"""The benchmark's yardstick: spec loading, the two drivers (train,
serve), traffic generation, trace reduction, FLOP counting, the table of
peaks and the comparison that decides ``correct``.  From the program it
takes the system under test (``train_net``, ``build_stack`` →
``ServingEngine.submit``) and its counters, nothing else."""
