"""Serving engine: mean share of the decode slots in use
(``step()["n_active"]`` over ``num_slots``) over the steps that had work."""


def read(ctx):
    occ = ctx["measured"].get("occupancy")
    if not occ:
        return None
    return 100.0 * sum(occ) / len(occ)
