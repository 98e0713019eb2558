"""Share of the decode slots that held a live request, averaged over the
decode steps of the window: the scheduler's ``mean_slot_occupancy``,
counted at the engine's ``generate`` calls (steps x live slots over steps
x slots)."""


def read(run):
    c = run.counters
    steps = c.get("decode_steps", 0)
    if not steps:
        return None
    return 100.0 * c["slot_steps"] / (steps * run.traffic["slots"])
