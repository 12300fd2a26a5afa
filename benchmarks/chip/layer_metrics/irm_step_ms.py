"""Mean wall milliseconds of one ``IRM.step``, over every step of every run
in the window, as ``run_live`` times them (``stats``)."""


def read(o):
    stats = o.layer.get("live_stats") or []
    ticks = sum(s["ticks"] for s in stats)
    if not ticks:
        return None
    return sum(s["irm_step_ms_mean"] * s["ticks"] for s in stats) / ticks
