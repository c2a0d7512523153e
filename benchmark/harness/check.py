"""The comparison that decides ``correct``: short plain names, each with its
number and its limit. A run is correct when every number is at or under its
limit; a number that could not be taken (None, NaN) fails."""

import math
import sys


def compare(numbers, limits):
    """``numbers``: {name: value}; ``limits``: {name: limit}. Every limit has
    to have its number. Returns (correct, {name: [value, limit]})."""
    table, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = (value is not None and not math.isnan(value)
                and value <= limit)
        ok = ok and good
        table[name] = [value, limit]
    return ok, table


def print_table(table, correct, stream=sys.stderr):
    for name, (value, limit) in table.items():
        shown = "none" if value is None else f"{value:.6g}"
        mark = "ok" if (value is not None and value <= limit) else "FAIL"
        print(f"compared {name} {shown} limit {limit:g} {mark}", file=stream)
    print(f"correct {str(bool(correct)).lower()}", file=stream, flush=True)
