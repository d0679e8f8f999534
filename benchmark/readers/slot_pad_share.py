"""Share of a dual chunked layout's stored slots that hold padding:
1 - 2 x (``entries`` - ``hot_entries``) / (``row_slots`` + ``col_slots``),
from the record of the build that the family keeps under
``run["counters"][params["counter"]]`` (every real entry that the chunks hold
is stored once a side; ``hot_entries`` are those that the busy bins' planes
hold instead, none where the record has no such key)."""


def read(run, params):
    record = run["counters"].get(params["counter"]) or {}
    try:
        slots = float(record["row_slots"]) + float(record["col_slots"])
        entries = float(record["entries"]) - float(
            record.get("hot_entries") or 0)
    except KeyError:
        return None
    if slots <= 0:
        return None
    return 100.0 * (1.0 - 2.0 * entries / slots)
