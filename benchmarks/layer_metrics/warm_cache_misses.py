"""Programs that set-up had to compile although the persistent cache was
there: 0 in a warm run of a program whose every jitted function is cacheable."""


def read(trace, counters, cell, config, peak):
    return counters["setup_cache"]["misses"]
