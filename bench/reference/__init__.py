"""Plain references of the disciplines the benchmark's cells run.

Each module is found by the ``reference`` name in a configuration file and
holds two implementations of one discipline's semantics, independent of
the program under test and of each other: a per-op model in plain Python
(``Plain``), and a vectorised numpy replay (``Replay``) fast enough to
check every op of a window in less time than the window took.  Tests hold
the two equal; the benchmark's comparison uses ``Replay``.
"""
