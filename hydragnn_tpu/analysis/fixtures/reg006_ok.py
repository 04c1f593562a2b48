"""Fixture: declared names only — spans, tracer regions, device scopes,
kernel names; unrelated ``.span()`` / ``.start()`` spellings
(re.Match.span, Thread.start) stay out of the rule's reach (REG006
quiet)."""

import re


class Traced:
    def flush(self, tr, t0, t1):
        tr.record_interval("serve.flush", t0, t1, n=3)
        with tr.span("serve.predict"):
            pass

    def regions(self, tr, tracer, thread, which):
        tr.start("train.dispatch")
        tr.stop("train.dispatch")
        with tracer.timer("data.collate"):
            pass
        tr.start(which)  # literal-only, like span
        thread.start("not a region")

    def scopes(self, comm_region, phase):
        with comm_region("comm.dp_psum"), phase("step.loss"):
            pass

    def kernels(self, pl, body, spec, kernel_name="gather_mul_seg_fwd"):
        pl.pallas_call(body, name="egcl_fwd")
        pl.pallas_call(body, name=f"{spec.name}_bwd_p")
        pl.pallas_call(body, name=kernel_name)
        self.kernels(pl, body, spec, kernel_name="gather_mul_seg_bwd")

    def offsets(self, text):
        m = re.match(r"\d+", text)
        return m.span(0) if m else None
