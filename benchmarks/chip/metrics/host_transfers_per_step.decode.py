"""host_transfers_per_step.decode: device-to-host transfers the engine made
per decode step, from the program's counters in the process registry (set-up
and window together): ``serving.host_transfers{phase=decode}`` over
``serving.decode_steps``.  None where either is 0 (observability off, or a
program without the counters)."""


def read(ctx):
    from repro import obs

    reg = obs.registry()
    transfers = reg.counter("serving.host_transfers").value(phase="decode")
    steps = reg.counter("serving.decode_steps").total()
    return transfers / steps if transfers and steps else None
