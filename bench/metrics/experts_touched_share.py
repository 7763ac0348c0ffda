"""Experts the decode steps touched (%): the distinct experts each paged
decode step's tokens routed to, summed over expert layers and steps
(``expert_loads_decode``), over the experts there were in those layers and
steps (``expert_slots_decode``).  None where the program keeps no such
counters."""


def read(ctx):
    stats = [q["stats"] for q in ctx["queries"] if q["stats"]]
    if not stats or "expert_slots_decode" not in stats[0]:
        return None
    slots = sum(s["expert_slots_decode"] for s in stats)
    return 100.0 * sum(s["expert_loads_decode"] for s in stats) / slots \
        if slots else None
