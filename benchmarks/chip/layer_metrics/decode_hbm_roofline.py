"""Decode's share of its roofline, in %: over the window's decode steps,
the sum of each step's bound (the larger of its FLOPs over the peak and
its bytes over the HBM bandwidth: every weight once plus the valid K/V
positions, ``flops.decode_step_bytes``) over the summed device time of the
decode program's executions in the trace."""

PROGRAM = r"^jit_step"


def read(o):
    s = o.layer.get("trace")
    fl, by = o.layer.get("decode_flops"), o.layer.get("decode_bytes")
    if s is None or not fl:
        return None
    times = s.module_times(PROGRAM)
    if len(times) < len(fl) or sum(times) <= 0:
        return None
    pk = o.layer["peaks"]
    bound = sum(max(f / pk["peak_flops_bf16"], b / pk["hbm_bytes_per_s"])
                for f, b in zip(fl, by))
    return 100.0 * bound / (sum(times) / s.n_devices)
