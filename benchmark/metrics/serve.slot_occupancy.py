"""Mean of `engine.active_slots`, sampled every 100 ms, over `max_slots`."""


def read(r):
    occ = r["counters"]["slot_occupancy"]
    return None if occ is None else 100.0 * occ
