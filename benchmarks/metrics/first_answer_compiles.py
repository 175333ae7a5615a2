"""Programs compiled anew in the first answer: xla_stats `compiles` less the
persistent cache's `cache_hits` over that execution."""


def read(run):
    c = run["first_answer"]["counters"]
    return c["compiles"] - c["cache_hits"]
