"""The benchmark's harness: everything here is general. What belongs to one
configuration, one traffic mix or one per-layer metric lives in a file of its
own under ``configs/``, ``adapters/``, ``traffic/`` or ``metrics/`` and is
found by the name in ``BENCHMARK.json``."""
