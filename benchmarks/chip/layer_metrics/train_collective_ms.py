"""Device ms per training step of the collective operations (all-gather,
reduce-scatter, all-reduce, their async starts and ends and the fusions
XLA names after them), by self time, mean over the chips."""

STEP = r"^jit_train_step$"
COLLECTIVES = r"all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all"


def read(o):
    s = o.layer.get("trace")
    if s is None or not s.n_devices:
        return None
    runs = len(s.module_times(STEP)) / s.n_devices
    if not runs:
        return None
    secs, _ = s.op_time(COLLECTIVES)
    return 1e3 * secs / runs
