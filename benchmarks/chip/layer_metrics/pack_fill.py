"""Share of the packed rows' slots, in %, that hold a real token: the
``real_tokens`` over the ``slots`` of the ``repro.data.pack`` spans (one
per batch the First-Fit packer emits) in the traced window."""


def read(o):
    pt = o.layer.get("program")
    if pt is None:
        return None
    packs = [s.ids for s in pt.spans_named("repro.data.pack")
             if "slots" in s.ids]
    slots = sum(int(p["slots"]) for p in packs)
    if not slots:
        return None
    return 100.0 * sum(int(p["real_tokens"]) for p in packs) / slots
