"""Mean device time of one execution of the jitted prefill (``jit_prefill``
on the trace's ``XLA Modules`` line) inside the traced slice (device
trace); None where the run read no module times."""

from harness import program


def read(run):
    return program.device_ms(getattr(run, "modules", None), "prefill")
