"""Share of the routed visits (rows x experts per token) that landed
on the experts this chip holds, over the window's fetched steps: the
program's `moe_rows_held_total` over `moe_rows_routed_total`.  A fair
router gives held / routed experts (16 / 128 = 0.125)."""


def read(run):
    routed = run.window_delta("moe_rows_routed_total")
    if not routed:
        return None
    return run.window_delta("moe_rows_held_total") / routed
