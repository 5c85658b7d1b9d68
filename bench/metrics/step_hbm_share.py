"""Model step: bytes the window's scheduler steps need (the served weights
once per step, and the valid KV of every slot each step attends, in its
stored format) over the time those steps took times the chips' HBM
bandwidth."""


def read(run):
    secs = sum(s.t1 - s.t0 for s in run.steps)
    if not secs:
        return None
    need = sum(run.counts.step_bytes(run.conf, run.kv_format, s.decode_ctx,
                                     s.prefill) for s in run.steps)
    return 100.0 * need / (secs * run.chips * run.peaks["hbm_bytes_per_s"])
