"""Peak device memory of the run, in GiB: ``peak_bytes_in_use`` of the
fullest chip, read after the window and before the reference runs."""


def read(rec, tr):
    b = rec.get("memory_peak_bytes")
    return None if not b else b / 2 ** 30
