"""Fixture: names the registry has never heard of (REG006)."""


class Traced:
    def flush(self, tr, t0, t1, which):
        tr.record_interval("serve.totally_undeclared", t0, t1)
        with tr.span("another.rogue_span"):
            pass
        # dynamic name: the registry rule cannot see it at all
        tr.record_interval(which, t0, t1)

    def regions(self, tr, phase, which):
        tr.start("train.made_up_region")
        with phase("step.rogue_phase"):
            pass
        with phase(which):
            pass

    def kernels(self, pl, body, spec, label):
        pl.pallas_call(body)
        pl.pallas_call(body, name="rogue_kernel_fwd")
        pl.pallas_call(body, name=f"{spec.name}_sideways")
        pl.pallas_call(body, name=label)
