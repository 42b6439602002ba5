"""Host seconds of the first call of each program the run compiles
(a load from the compile cache when warm), from the benchmark's spans."""


def read(rec, tr):
    v = rec.get("spans", {}).get("compile")
    return None if v is None else v
