"""A program counter's gain over the window: `run.counter(name)` less
`run.counter(name, at_open=True)`, times `scale`.  For the series the
program's heartbeat feeds from the kernel's accounting
(`tpums_host_cpu_throttled_seconds_total`,
`tpums_host_runqueue_wait_seconds_total`, ...), which it reads once a
second: exact to one such period at either end of the window (the `stalls`
reader's entries carry the exact deltas).  Nothing is returned where the
program has no such series: a program from before it, or a host that lacks
the file behind it."""


def read(run, name, scale=1.0):
    closed = run.counter(name)
    if closed is None:
        return None
    return (closed - (run.counter(name, at_open=True) or 0)) * scale
