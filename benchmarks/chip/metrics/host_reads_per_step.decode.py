"""host_reads_per_step.decode: device-to-host reads the engine issued per
decode step, from the program's counters in the process registry (set-up and
window together): ``serving.host_reads{phase=decode}`` over
``serving.decode_steps``.  None where either is 0 (observability off, or a
program without the counters)."""


def read(ctx):
    from repro import obs

    reg = obs.registry()
    reads = reg.counter("serving.host_reads").value(phase="decode")
    steps = reg.counter("serving.decode_steps").total()
    return reads / steps if reads and steps else None
