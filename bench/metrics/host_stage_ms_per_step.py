"""host_stage_ms_per_step (ms): host time in the program's
``tlora.stage`` spans (``GroupRuntime._stage``: batch assembly and the
host-to-device put) inside the traced window, per step.  The window's
last dispatch stages the chunk after it, so this is the staging work
the host did while the window ran."""
import scopes


def read(ctx):
    sc = scopes.of(ctx)
    if sc is None or not ctx.steps:
        return None
    t = sum(max(0, min(s.end, ctx.hi) - max(s.start, ctx.lo))
            for s in sc.spans if s.name == "tlora.stage")
    return t / 1e6 / ctx.steps if t else None
