"""The benchmark's shared code: everything every cell uses.

Only ``program.py`` (the model and the engine) and ``program_spans.py``
(the step records the program writes) import the system under test.
``weights.py``, ``traffic.py``, ``counts.py``, ``xtrace.py`` and the
references under ``benchmark/references/`` import nothing of it: they are
the yardstick.
"""
