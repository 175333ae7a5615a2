"""Seconds from a loaded process to the first answer of the cell's query:
the host clock around the first to_arrow(), taken before any trace starts."""


def read(run):
    return run["first_answer"]["seconds"]
