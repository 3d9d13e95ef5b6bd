"""Faults planted in a cell's timed path: a run with any of them has to
read ``correct: false``.

Each fault takes what a driver hands to ``Context.fault`` and breaks it
where the answer is produced: the staged kernel for ``staged_loop``, the
scheduler for ``closed_loop``.  The tests plant them on the CPU at tiny
sizes; ``control.py --fault <name>`` reads them at a cell's own size.
"""
from __future__ import annotations


# ---------------------------------------------------------------------- #
# staged_loop: the staged kernel, called as kern(val, x)
# ---------------------------------------------------------------------- #
def altered(kern):
    """One entry of every result altered."""
    return lambda val, x: kern(val, x).at[3].add(1.0)


def half_left_out(kern):
    """The second half of every result's rows left at zero."""
    def fn(val, x):
        y = kern(val, x)
        return y.at[y.shape[0] // 2:].set(0.0)
    return fn


def unchanged(kern):
    """The call returns its operand: the iteration's state never moves."""
    return lambda val, x: x


# ---------------------------------------------------------------------- #
# closed_loop: the scheduler
# ---------------------------------------------------------------------- #
def token_altered(sched):
    """Every decoded token is replaced by the next one in the vocabulary."""
    step = sched._lane_step

    def altered_step(*args):
        nxt, logits, slices = step(*args)
        return (nxt + 1) % sched.cfg.vocab_size, logits, slices
    sched._lane_step = altered_step


def state_unchanged(sched):
    """The decode step's cache writes never reach the pages."""
    sched.kv.append_token = lambda rid, slices, position: sched.kv.seq_len.__setitem__(
        rid, max(sched.kv.seq_len[rid], position + 1))


def half_batch_left_out(sched):
    """Half of the lanes decode from an empty cache view."""
    gather = sched.kv.gather

    def half(rids):
        return gather([r if i % 2 == 0 else None for i, r in enumerate(rids)])
    sched.kv.gather = half


BY_DRIVER = {
    "staged_loop": {"altered": altered, "half_left_out": half_left_out,
                    "unchanged": unchanged},
    "closed_loop": {"token_altered": token_altered, "state_unchanged": state_unchanged,
                    "half_batch_left_out": half_batch_left_out},
}
