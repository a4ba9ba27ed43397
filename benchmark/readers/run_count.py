"""A number the driver left in `run.counts` (a check's reading, a count of
work), times `scale`; nothing where the driver left none."""


def read(run, name, scale=1.0):
    value = run.counts.get(name)
    return None if value is None else value * scale
