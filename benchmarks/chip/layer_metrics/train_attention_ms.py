"""Device ms per training step under the named scope ``attention`` (the
forward, its recompute and the backward of every layer's attention), mean
over the chips, from the program's scopes (``program_trace``)."""

STEP = r"^jit_train_step$"


def read(o):
    pt = o.layer.get("program")
    if pt is None:
        return None
    return pt.scope_ms(STEP, "attention")
