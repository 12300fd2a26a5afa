"""The grouped-matmul kernel's share of its roofline, in %: per call, the
larger of its FLOPs over the peak and its bytes over the HBM bandwidth
(``flops.gmm_call``), over the kernel's mean device time in the trace."""

KERNEL = r"^grouped_matmul$"


def read(o):
    s, call = o.layer.get("trace"), o.layer.get("gmm_call")
    if s is None or call is None:
        return None
    secs, n = s.op_time(KERNEL)
    if not n or secs <= 0:
        return None
    pk = o.layer["peaks"]
    bound = max(call["flops"] / pk["peak_flops_bf16"],
                call["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * bound * n / secs
