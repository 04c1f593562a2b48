"""jax.profiler wrapper (parity: reference hydragnn/utils/profile.py:9-70).

The reference wraps ``torch.profiler.profile`` with a wait/warmup/active
schedule and a TensorBoard trace handler, enabled per-epoch from the config's
``Profile`` section.  Here the same schedule gates ``jax.profiler`` traces
(viewable in TensorBoard/Perfetto/XProf).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

from hydragnn_tpu.utils.env import env_flag, env_int, env_str


class Profiler:
    """Step-scheduled profiler: wait -> warmup -> active -> done.

    Config keys (reference profile.py:32-43): ``enable`` (int), ``wait``,
    ``warmup``, ``active``, ``trace_dir``.  Env knobs override the config
    (env wins, matching every other overlay in the tree), so a device
    trace can be captured on a deployed config without editing it:
    ``HYDRAGNN_PROFILE`` (enable), ``HYDRAGNN_PROFILE_WAIT``,
    ``HYDRAGNN_PROFILE_WARMUP``, ``HYDRAGNN_PROFILE_ACTIVE`` (schedule
    steps), ``HYDRAGNN_PROFILE_DIR`` (trace output directory).
    """

    def __init__(self, config: Optional[Dict[str, Any]] = None,
                 log_name: str = "run", logs_dir: str = "./logs/"):
        config = config or {}
        self.enabled = bool(int(config.get("enable", 0)))
        self.wait = int(config.get("wait", 5))
        self.warmup = int(config.get("warmup", 3))
        self.active = int(config.get("active", 3))
        self.trace_dir = config.get(
            "trace_dir", os.path.join(logs_dir, log_name, "trace"))
        if "HYDRAGNN_PROFILE" in os.environ:
            self.enabled = env_flag("HYDRAGNN_PROFILE")
        if "HYDRAGNN_PROFILE_WAIT" in os.environ:
            self.wait = env_int("HYDRAGNN_PROFILE_WAIT", self.wait)
        if "HYDRAGNN_PROFILE_WARMUP" in os.environ:
            self.warmup = env_int("HYDRAGNN_PROFILE_WARMUP", self.warmup)
        if "HYDRAGNN_PROFILE_ACTIVE" in os.environ:
            self.active = env_int("HYDRAGNN_PROFILE_ACTIVE", self.active)
        if "HYDRAGNN_PROFILE_DIR" in os.environ:
            self.trace_dir = env_str("HYDRAGNN_PROFILE_DIR", self.trace_dir)
        self._step = 0
        self._stop_at = 0
        self._tracing = False
        self._done = False

    def setup(self, config: Optional[Dict[str, Any]]):
        """Re-arm from a config section (reference Profiler.setup)."""
        if config:
            self.__init__(config, os.path.basename(
                os.path.dirname(self.trace_dir)) or "run",
                os.path.dirname(os.path.dirname(self.trace_dir)) or "./logs/")
        return self

    def step(self, n: int = 1) -> None:
        """Advance the schedule by the ``n`` optimizer steps of the
        dispatch just made (scan-K makes K a call), so ``wait``,
        ``warmup`` and ``active`` count steps whatever K is.  The trace
        opens once ``wait + warmup`` steps are dispatched and closes once
        ``active`` more are: at least one whole dispatch."""
        if not self.enabled or self._done:
            return
        self._step += max(1, int(n))
        if self._tracing:
            if self._step >= self._stop_at:
                import jax.profiler

                jax.profiler.stop_trace()
                self._tracing = False
                self._done = True
        elif self._step >= self.wait + self.warmup:
            import jax.profiler

            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir)
            self._tracing = True
            self._stop_at = self._step + self.active

    def disable(self):
        if self._tracing:
            import jax.profiler

            jax.profiler.stop_trace()
            self._tracing = False
        self.enabled = False
