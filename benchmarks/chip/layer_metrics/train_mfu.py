"""The training step's share of the chips' bf16 peak, in %: the model
FLOPs of the timed steps' real tokens (``flops_train.train_flops``: 6 per
weight a token, the tied head included, and causal attention within each
document; no recompute, padding or masked work) over the window's wall
time, over the chips' summed peak."""


def read(o):
    work = o.layer.get("model_flops")
    if not work:
        return None
    rate = work / o.layer["window_s"]
    return 100.0 * rate / (o.layer["n_chips"]
                           * o.layer["peaks"]["peak_flops_bf16"])
