"""The fullest held expert's rows over the mean held expert's, over
the window's fetched steps and the expert layers (1 = balanced): from
the program's `moe_expert_rows_max_total` and `moe_rows_held_total`
counters, fed from the count vector every fetched step returns."""


def read(run):
    held = run.window_delta("moe_rows_held_total")
    if not held:
        return None
    return (run.window_delta("moe_expert_rows_max_total")
            * run.config["num_experts"] / held)
