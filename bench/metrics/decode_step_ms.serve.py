"""Device time of the engine's jitted decode program per decode step."""


def read(run):
    steps = run.counters.get("decode_steps", 0)
    t = run.trace.program_s.get("decode") if run.trace else None
    if not steps or not t:
        return None
    return 1e3 * t / steps
