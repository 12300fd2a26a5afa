"""Mean ms of the step loop's wait for its next device batch: the
``repro.train.batch`` spans (the packer's queue, then the transfer to the
mesh) in the traced window."""


def read(o):
    pt = o.layer.get("program")
    if pt is None:
        return None
    waits = [(s.end - s.start) * 1e-6
             for s in pt.spans_named("repro.train.batch")]
    return sum(waits) / len(waits) if waits else None
