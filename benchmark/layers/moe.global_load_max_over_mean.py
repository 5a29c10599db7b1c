"""The fullest of ALL the router's outputs over their mean, over the
window's fetched steps and the expert layers (1 = balanced; held here
or not): from the program's `moe_router_rows_max_total` and
`moe_router_rows_total` counters, fed from the load vectors every
fetched step returns — what the selection bias's update works on."""


def read(run):
    rows = run.window_delta("moe_router_rows_total")
    if not rows:
        return None
    return (run.window_delta("moe_router_rows_max_total")
            * run.config["router_width"] / rows)
