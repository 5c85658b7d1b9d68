"""Decode kernels: the least time the decode attend kernels could take
(the larger of their needed bytes over HBM bandwidth and their int8
operations over the int8 peak) over the device time of the kernel launches
inside ``jit_serve_step``, summed over devices.  Steps are those the host
ran inside the traced window."""


def read(run):
    if run.trace is None:
        return None
    kernel_s = sum(d.kernel_ns for d in run.trace.devices) * 1e-9
    if not kernel_s:
        return None
    c, conf = run.counts, run.conf
    need = sum(c.attend_bytes(conf, run.kv_format, s.decode_ctx)
               for s in run.traced_steps)
    ops = sum(c.attend_flops(conf, x) for s in run.traced_steps
              for x in s.decode_ctx)
    least = max(need / run.peaks["hbm_bytes_per_s"],
                ops / run.peaks["int8_ops_per_s"])
    return 100.0 * least / kernel_s
