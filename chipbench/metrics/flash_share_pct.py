"""Share of the device's busy time spent in the flash-attention kernels
(forward, dq, dk/dv). In the trace they are the operations named after
the custom differentiation rule that holds the Pallas calls
(``jvp...`` forward, ``transpose_jvp...`` backward). Layer: train
kernels."""


def is_flash(op) -> bool:
    return op.name.startswith(("jvp", "transpose_jvp"))


def read(run):
    s = run.summary
    if s is None or s.busy_s <= 0:
        return None
    seconds = s.seconds_where(is_flash)
    return 100.0 * seconds / s.busy_s if seconds > 0 else None
