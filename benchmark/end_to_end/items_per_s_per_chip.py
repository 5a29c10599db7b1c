"""Training items (BERT: tokens = batch x seq, padding included;
ResNet: images) of all steps completed inside the window, over the
window's own seconds, over the chips of the cell."""


def read(run):
    return (run.window.steps * run.system.items_per_step
            / run.window.seconds / run.chips)
