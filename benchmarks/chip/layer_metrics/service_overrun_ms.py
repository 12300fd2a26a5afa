"""Mean wall milliseconds by which an image's service outlasted its
calibrated duration: the host and device time that the payload's padding
sleep did not absorb."""


def read(o):
    v = o.layer.get("overrun_ms")
    return sum(v) / len(v) if v else None
