"""Share of a window instance's forward grid steps that stand on a live
tile: the program's `flash_window_tiles_live_total` over
`flash_window_grid_steps_total`, counted a head where the instances are
traced (set-up).  1.0 would be a grid with no dead step at all; a grid
that walks the band reads just under it (the first q tiles' bands are
shorter than the grid's inner axis: 189 of 192 at (256, 256) tiles), a
rectangle of skipped steps 189 of 4,096."""


def read(run):
    steps = run.setup_delta("flash_window_grid_steps_total")
    if not steps:
        return None
    return run.setup_delta("flash_window_tiles_live_total") / steps
