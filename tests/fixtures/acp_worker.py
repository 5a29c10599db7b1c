"""Auto-checkpoint preemption fixture: trains N epochs; if PREEMPT_AT is
set, kills itself (simulated preemption) at the END of that epoch,
after the checkpoint save.  Writes per-epoch losses to OUT."""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, "/root/repo")
import numpy as np
import paddle_tpu.fluid as fluid
import paddle_tpu.fluid.incubate.checkpoint.auto_checkpoint as acp

out_path = sys.argv[1]
preempt_at = int(os.environ.get("PREEMPT_AT", "-1"))

main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.data("x", [-1, 8], "float32")
    yt = fluid.data("yt", [-1, 1], "float32")
    pred = fluid.layers.fc(x, 1)
    loss = fluid.layers.reduce_mean(
        fluid.layers.loss.square_error_cost(pred, yt))
    fluid.optimizer.SGD(0.1).minimize(loss)
exe = fluid.Executor()
exe.run(startup)

W = np.random.RandomState(42).randn(8, 1).astype("float32")
losses = []
r = acp.train_epoch_range(6, program=main)
for epoch in r:
    rng = np.random.RandomState(100 + epoch)  # per-epoch data, restart-stable
    for _ in range(20):
        X = rng.randn(16, 8).astype("float32")
        L, = exe.run(main, feed={"x": X, "yt": X @ W}, fetch_list=[loss])
    losses.append(float(L))
    with open(out_path, "a") as f:
        f.write(f"{epoch} {float(L):.8f}\n")
    if epoch == preempt_at:
        os._exit(17)  # simulated preemption AFTER this epoch's save...
        # (train_epoch_range saves after the yield resumes; see test)
print("restored_epoch:", r.restored_epoch)
