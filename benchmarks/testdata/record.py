#!/usr/bin/env python3
"""Records the small trace `reduce.py` is tested on, on the chip:

    python benchmarks/testdata/record.py [out_dir]

Two jitted functions with names of their own run a known number of
times with the device left idle for a known time in between; the trace
goes to <out_dir>/tiny.xplane.pb and what was done to
<out_dir>/tiny.expected.json.  Copy both into benchmarks/testdata/.
"""

import glob
import json
import os
import shutil
import sys
import time


def main(out):
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("record: needs the chip", file=sys.stderr)
        return 2

    @jax.jit
    def tiny_scale(x):
        return (x * 2.0 + 1.0).sum()

    @jax.jit
    def tiny_gather(x, idx):
        return x.reshape(-1)[idx].sum()

    x = jnp.ones((1024, 1024), jnp.float32)
    idx = jnp.arange(0, 1 << 20, 7, dtype=jnp.int32)
    tiny_scale(x).block_until_ready()
    tiny_gather(x, idx).block_until_ready()

    runs = {"tiny_scale": 5, "tiny_gather": 3}
    pause_s = 0.05
    tmp = os.path.join(out, "tiny_profile")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(runs["tiny_scale"]):
        tiny_scale(x).block_until_ready()
    time.sleep(pause_s)
    for _ in range(runs["tiny_gather"]):
        tiny_gather(x, idx).block_until_ready()
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))[0]
    shutil.copyfile(found, os.path.join(out, "tiny.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    with open(os.path.join(out, "tiny.expected.json"), "w") as fp:
        json.dump({"runs": runs, "pause_s": pause_s,
                   "device_kind": jax.devices()[0].device_kind}, fp)
    return 0


if __name__ == "__main__":
    out = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/bench"
    os.makedirs(out, exist_ok=True)
    sys.exit(main(out))
