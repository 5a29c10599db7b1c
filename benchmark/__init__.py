"""The benchmark: the yardstick every later PR is measured with.

Nothing in here is imported by paddle_tpu; see benchmark/README.md."""
