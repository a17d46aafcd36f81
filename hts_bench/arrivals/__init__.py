"""Arrival processes: ``<kind>.due_times(rng, arrivals, seconds)`` gives
the due times of a traffic file whose ``arrivals.kind`` is ``<kind>``."""
