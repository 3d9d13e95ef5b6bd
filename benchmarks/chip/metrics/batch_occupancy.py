"""``batch_occupancy``: decode tokens over (scheduler steps x max_batch) in
the window, in %, from the scheduler's own counters (``sched.stats``)."""


def read(run):
    c = run.outcome.counters
    if not c.get("sched_steps"):
        return None
    return 100.0 * c["sched_decode_tokens"] / (c["sched_steps"] * c["max_batch"])
