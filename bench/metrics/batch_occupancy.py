"""batch_occupancy (%): decoding slots per decode iteration over the slot
count, from what each ``step()`` of the window emitted (scheduler layer)."""


def read(rec):
    steps = [s for s in rec.steps if s.decode_ctx]
    if not steps:
        return None
    slots = rec.run.serving["num_slots"]
    return 100.0 * sum(len(s.decode_ctx) for s in steps) / (len(steps)
                                                            * slots)
