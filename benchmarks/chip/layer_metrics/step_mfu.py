"""The model's share of the chip's bf16 peak, in %: the model FLOPs of all
the prefill and decode work in the window (``flops.prefill_flops``,
``flops.decode_step_flops``), over the window's wall time."""


def read(o):
    work = o.layer.get("model_flops")
    if not work:
        return None
    rate = work / o.layer["window_s"]
    return 100.0 * rate / o.layer["peaks"]["peak_flops_bf16"]
