"""Mean share of the pool's slots that a step's decode sub-steps served,
over the window's steps, in %."""


def read(rec):
    if not rec.ticks:
        return None
    return 100.0 * sum(t.resident for t in rec.ticks) / (len(rec.ticks) * rec.max_slots)
