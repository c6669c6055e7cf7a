"""Scheduler: share of decode lanes busy, busy lane-steps over steps times
lanes, over the window (%)."""


def read(rec):
    if not rec.get("steps"):
        return None
    return 100.0 * rec["lane_steps"] / (rec["steps"] * rec["num_slots"])
