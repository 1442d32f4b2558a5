"""Share of the sparse contact windows' neighbour slots that hold a contact,
self slots included, in percent: the program's ``fed.contact_edges`` over
its ``fed.contact_slots`` counters, summed over the timed federations of
the traced window (bench.spans)."""
from bench import spans


def read(run):
    win = spans.window(run, "contact_slot_use")
    if win is None:
        return None
    slots = win.total_count("fed.contact_slots")
    if not slots:
        spans.note("contact_slot_use", "no sparse contact window was counted")
        return None
    return 100.0 * win.total_count("fed.contact_edges") / slots
