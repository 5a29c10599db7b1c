"""Seconds inside the Pallas entry points while a program is traced
(`flash_attention()` and its ladder's probes, `kda_attention()`, the
rules of their `custom_vjp`): self time of the program's
`setup.kernel_trace` phase, outside the reference check."""

from benchmark.lib import setup_phases


def read(run):
    return setup_phases.of_run(run, "setup.kernel_trace_s")
