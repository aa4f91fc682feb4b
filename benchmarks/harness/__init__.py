"""The benchmark's harness: the yardstick every later PR is measured with.

Nothing here is imported by the program; from the program the harness
takes only the system under test (``paddle_tpu``'s public entry points),
its counters and its kernel-path counts.
"""
