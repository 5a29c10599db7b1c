"""A system that does nothing, to show that a new configuration is a
new file: the harness is not edited for it."""


class Dummy:
    untrained_loss = 1.0
    first_loss_band = 1.0
    flops_per_item = 1.0
    kernels = {}
    kernel_ops = {}
    memory_analysis = {}
    reference = {"ok": True}

    def __init__(self, config, traffic, spans):
        self.items_per_step = config["items"]
        self.pool = list(range(traffic["pool_batches"]))
        self.spans = spans
        self.stepped = 0

    def step(self, batch):
        with self.spans.span("bench.dispatch"):
            self.stepped += 1
            return 1.0 + 0.1 * batch

    def fetch(self, handle):
        return handle

    def sync(self):
        pass

    def close(self):
        pass

    def checks(self, counters_now, first_loss):
        return {"dummy_stepped": self.stepped > 0}


def build(config, traffic, chips, seed, spans):
    return Dummy(config, traffic, spans)
