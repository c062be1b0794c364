"""Set-up: from the start of the process to the start of the window — making
the tables from the seed, starting the servers, and the warm-up sends with
whatever they trace, compile (first run of a checkout) or load from the
compile cache, and place on the device."""

META = {"unit": "s", "better": "lower", "source": "host_clock"}


def compute(run):
    return run.setup_s
