"""Chip benchmark of the served GPTQ path: weights, traffic, trace reduction,
work counts and the output check, driven by ``benchmarks/chip/run.py``.

Nothing here is imported by the program under test.  Only ``program.py``
imports the program (``repro``); ``references/`` and the modules here stay
independent of it.
"""
