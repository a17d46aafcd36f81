"""Generator kinds: ``<kind>.generate(seed, traffic, seconds)`` builds the
:class:`hts_bench.gen.Traffic` of a traffic file whose ``generator`` is
``<kind>``."""
