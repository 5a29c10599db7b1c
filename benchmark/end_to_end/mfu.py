"""Model FLOP/s utilisation as a share (0-1): items/s/chip x the FLOPs
the model needs per item (benchmark/lib/flops.py) over the chip's
published bf16 peak — also for a float32 Program, whose matmuls the
chip runs in bf16 passes.  Nothing without a peak for the device."""


def read(run):
    if run.peaks is None:
        return None
    return (run.read("end_to_end", "items_per_s_per_chip")
            * run.system.flops_per_item / run.peaks["flops"])
