"""The chip benchmark's own code: the yardstick later PRs may not change.

Nothing here is imported by the program, and nothing here imports a helper
of ``bench.py``, ``bench_batch.py`` or ``oryx_tpu/tools``.
"""
