"""Record the small annotated chip trace that ``test_host_spans.py``
reads.

    python benchmarks/tests/record_annotated_trace.py <out.xplane.pb>

Run on a TPU, with the benchmark's profiler options: four "batches",
each the serving runner's stage spans (``obs.trace.span``, tagged with
the batch number) around a jitted step, with idle time under
``serve.wait``, ``worker.prep``, ``worker.fetch`` and ``serve.finish``,
and 5 ms with no span open between batches.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import trace_reduce  # noqa: E402
from distributed_oracle_search_tpu.obs import trace as obs_trace  # noqa: E402

BATCHES = 4


@jax.jit
def bench_annotated_step(x):
    return jnp.tanh(x @ x.T).sum(axis=0)


def main() -> int:
    host = np.ones((512, 512), np.float32)
    bench_annotated_step(jnp.asarray(host)).block_until_ready()
    d = tempfile.mkdtemp()
    trace_reduce.start_trace(d)
    for b in range(BATCHES):
        with obs_trace.tagged(batch=b, size=8):
            with obs_trace.span("serve.wait"):
                time.sleep(0.004)
            with obs_trace.span("worker.prep"):
                x = jnp.asarray(host)
                time.sleep(0.002)
            with obs_trace.span("worker.walk"):
                y = bench_annotated_step(x)
                y.block_until_ready()
            with obs_trace.span("worker.fetch"):
                np.asarray(y)
            with obs_trace.span("serve.finish"):
                time.sleep(0.003)
        time.sleep(0.005)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, sys.argv[1])
    shutil.rmtree(d)
    return 0


if __name__ == "__main__":
    sys.exit(main())
