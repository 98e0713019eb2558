"""Device time of the engine's jitted prefill program per prefill (one
prompt padded to the mix's ``prompt_pad``)."""


def read(run):
    n = run.counters.get("prefills", 0)
    t = run.trace.program_s.get("prefill") if run.trace else None
    if not n or not t:
        return None
    return 1e3 * t / n
