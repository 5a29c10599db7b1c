"""Device milliseconds a step spends in the flash-attention kernels of
the latent attention (192-wide q/k heads over 128-wide v heads, causal
by tile class: forward, its recomputation where the configuration
recomputes, and both backward calls), from the device trace, by HLO
instruction."""


def read(run):
    t = run.trace
    if not t:
        return None
    seconds = sum(s for kind, s in t["kernel_s"].items()
                  if kind.startswith("flash_"))
    return seconds / t["steps"] * 1e3 if seconds else None
