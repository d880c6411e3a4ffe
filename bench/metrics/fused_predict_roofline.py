"""fused_predict's share of its roofline in the traced slice, in %: the least
time (bench/harness/work.py Run.roofline) over its summed device time."""


def read(run):
    return run.roofline("fused_predict")
