"""Traced runs: the toy stage program's share of its roofline: the least
time the device's peaks allow for the work of every stage dispatched in
the window (the plug-in's ``work``) over the device time of the program
``jit_toy_stage``; in percent."""
from bench import peaks


def read(w):
    p = peaks.peak(w.device_kind)
    if p is None or not w.device_programs or w.work is None:
        return None
    device_s = sum(s for name, (s, _) in w.device_programs.items()
                   if name.startswith("jit_toy_stage("))
    if not device_s:
        return None
    flops = nbytes = 0.0
    for workload, kinds, m in w.dispatched:
        for stage in kinds:
            f, b = w.work(workload, stage, m)
            flops, nbytes = flops + f, nbytes + b
    return 100.0 * peaks.roofline_seconds(flops, nbytes, p) / device_s
