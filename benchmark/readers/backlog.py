"""Mean order-queue backlog (published minus committed), from the benchmark's
10 ms samples inside the window."""


def read(run, meta):
    samples = run["backlog"]
    if not samples:
        return None
    return sum(samples) / len(samples)
