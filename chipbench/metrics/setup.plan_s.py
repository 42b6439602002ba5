"""Host seconds of ``plan()``: the planner's solve for the cell's fleet
and batch, from the benchmark's span around it."""


def read(rec, tr):
    return rec.get("spans", {}).get("plan")
