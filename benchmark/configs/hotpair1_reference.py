"""The plain reference of the `hotpair1` venue: one symbol, price-time priority,
one order at a time, in stream order. The matching rules are
benchmark/reference.py's; this file says which of them the venue states as its
guarantee and which control breaks it. It imports nothing of the program.
"""

from benchmark import reference

#: The guarantee the configuration states, and the control that breaks it.
PRIORITY = "fifo"
CONTROL_PRIORITY = "lifo"


def run(cols, priority=PRIORITY):
    """Events of the stream columns; see benchmark.reference.run."""
    return reference.run(cols, priority)
