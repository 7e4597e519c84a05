"""BCPNN serving: streaming (camera/NIC) and batched classification.

    PYTHONPATH=src python examples/streaming_bcpnn.py

Compiles a declarative network once, then serves it through the unified
front door — ``compiled.serve(ServiceConfig(plan=...))``:

* ``plan="streaming"`` wraps a StreamingSession (host-side coalescing into
  micro-batches without changing the EWMA semantics, LRU-bounded per-shape
  jit cells, learned state adopted into the compiled NetworkState on
  close) — the paper's latency-oriented operation mode;
* ``plan="batched"`` runs bucket-padded classification through the SAME
  cached jitted forward ``compiled.predict`` uses — the throughput mode.
"""
import time

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.core import ExecutionConfig, Network, StructuralPlasticityLayer, UnitLayout
from repro.data import complementary_code, mnist_like
from repro.runtime import ServiceConfig


def main():
    enable_compile_cache()
    ds = mnist_like(n_train=1024, n_test=64, n_features=64, seed=0)
    x, layout = complementary_code(ds.x_train)

    hidden = UnitLayout(8, 16)
    net = Network(seed=0).add(
        StructuralPlasticityLayer(
            layout, hidden, fan_in=32, lam=0.05, gain=4.0, init_jitter=1.0
        )
    )
    compiled = net.compile(ExecutionConfig())

    # --- streaming plan: online updates + single-sample inference --------
    svc = compiled.serve(ServiceConfig(plan="streaming", max_batch=16))

    t0 = time.perf_counter()
    for row in x[:512]:
        svc.feed(row)  # flushes every 16 samples
    svc.flush()
    dt = time.perf_counter() - t0
    print(f"streamed 512 training samples in {dt:.2f}s "
          f"({svc.stats['flushes']} micro-batch flushes)")

    t0 = time.perf_counter()
    n = 100
    for i in range(n):
        out = svc.infer(x[i])
    dt = time.perf_counter() - t0
    print(f"single-sample inference: {n/dt:.0f} samples/s "
          f"(paper: 28k-87k img/s on V100/A100)")
    print(f"activation of sample 0 (first HCU): {np.round(out[:16], 3)}")
    print(f"service stats: {svc.stats}")

    svc.close()  # adopt the streamed state into compiled.state
    print(f"compiled network now at step {int(compiled.state.layers[0].step)}")

    # --- batched plan: padded-bucket classification, shared forward ------
    batched = compiled.serve(
        ServiceConfig(plan="batched", max_batch=256, buckets=(64, 256))
    )
    scores = batched.predict(x[:100])  # padded to the 256 bucket
    print(f"batched predict on 100 samples -> {scores.shape} scores "
          f"({batched.stats['padded_rows']} pad rows, sliced off)")


if __name__ == "__main__":
    main()
