"""Model step: model FLOPs of the window's scheduler steps over the time
those steps took on the host times the chips' bf16 peak.  Decode and
prefill tokens, attention over each token's context included; no padding,
empty slots or replicated work."""


def read(run):
    secs = sum(s.t1 - s.t0 for s in run.steps)
    if not secs:
        return None
    flops = sum(run.counts.step_flops(run.conf, s.decode_ctx, s.prefill)
                for s in run.steps)
    return 100.0 * flops / (secs * run.chips * run.peaks["bf16_flops_per_s"])
