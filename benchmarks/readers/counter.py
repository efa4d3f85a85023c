"""A counter of the program, as the kind put it into the reading
(``reading["counters"][name]``): what the step's expert layers counted over
the timed window.  A reading without that counter (another kind of cell, a
program that counts no such thing) gives nothing to read."""


def read(ctx, reading, name):
    return reading.get("counters", {}).get(name)
