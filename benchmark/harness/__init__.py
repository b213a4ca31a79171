"""The benchmark's shared code: everything every cell uses.

Only ``program.py`` imports the system under test.  ``weights.py``,
``traffic.py``, ``counts.py``, ``xtrace.py`` and the references under
``benchmark/references/`` import nothing of it: they are the yardstick.
"""
