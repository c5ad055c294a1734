"""The benchmark of deepspeed_tpu: the yardstick later PRs are held to.

Everything here is the benchmark's own copy — load generation, the
arithmetic from stamps, spans and traces to metrics, the table of
peaks, each kernel's operations and bytes, the seeded weights and the
plain reference that decides ``correct``.  From the program it takes
only the system under test (``deepspeed_tpu.initialize`` →
``train_batch``; ``init_inference`` → ``ServingEngine``), its counters
and its kernel names.  ``BENCHMARK.json`` at the root of the repository
names the cells; ``run.py`` runs one.
"""
