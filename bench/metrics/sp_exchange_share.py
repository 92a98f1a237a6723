"""Share of the busiest chip's busy time in the traced steps spent in the
sharded scan's boundary exchange: the device time of the ops the compiler
emitted under ``gspn_sp.exchange`` (every collective op where the
compiled step carries no such scope; the run logs which) over that chip's
busy time."""


def read(run):
    ex = run.records.get("exchange")
    if not ex:
        return None
    i = ex["fullest"]
    busy = ex["busy_ns"][i]
    return None if busy <= 0 else 100.0 * ex["exchange_ns"][i] / busy
