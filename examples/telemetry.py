"""End-to-end pipeline telemetry (DESIGN.md §11).

Trace a tuned SpMV build + execution under a JAX profiler session, then
export the three observability surfaces: the profile (the pipeline's
spans beside the device operations, on one clock), the metrics snapshot,
and the per-launch cost report.

    PYTHONPATH=src python examples/telemetry.py [trace_dir report.json]

Span records (``trace.tree_dump()``) are enabled programmatically
(``trace.enable()``); in a process you don't control, set
``REPRO_TRACE=1`` in the environment instead.  The profile needs only the
profiler session.  ``REPRO_LOG=info`` additionally routes pipeline
warnings to stderr through the ``repro.*`` logger hierarchy.
"""
import glob
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import ProfileData

from repro.core.apps import SpMV
from repro.obs import metrics, trace
from repro.sparse import generators as G

trace_dir = sys.argv[1] if len(sys.argv) > 1 else "trace"
report_path = sys.argv[2] if len(sys.argv) > 2 else "report.json"

trace.enable()

# ---- build with input-adaptive tuning, run a few matvecs, profiled
m = G.power_law(n=2048, avg_deg=8)
with jax.profiler.trace(trace_dir, create_perfetto_trace=True):
    sp = SpMV.from_coo(np.asarray(m.rows), np.asarray(m.cols),
                       np.asarray(m.vals), m.shape, backend="auto")
    x = jnp.asarray(np.random.default_rng(0).standard_normal(m.shape[1]),
                    jnp.float32)
    for _ in range(3):
        y = sp.matvec(x)
    jax.block_until_ready(y)
print(f"matvec ok: {m.name} {m.shape} nnz={m.nnz} "
      f"chosen={sp.tuning.best.label} picked_by={sp.tuning.picked_by}")

# ---- surface 1: the span tree (text) and the profile
print("\nspan tree (truncated):")
print("\n".join(trace.tree_dump().splitlines()[:12]))
(xplane,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
profile = ProfileData.from_file(xplane)
host = {ev.name for plane in profile.planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events}
print(f"\nwrote {os.path.dirname(xplane)}: {len(host)} host event names "
      "(perfetto_trace.json.gz opens at ui.perfetto.dev; the .xplane.pb "
      "in TensorBoard's profile plugin)")

# ---- surface 2: the metrics registry
snap = metrics.snapshot()
interesting = {k: v for k, v in sorted(snap["counters"].items())
               if not k.startswith("test.")}
print(f"counters: {interesting}")

# ---- surface 3: the per-launch cost report
rep = sp.report()
with open(report_path, "w") as f:
    f.write(rep.to_json())
d = rep.to_dict()
print(f"wrote {report_path}: {d['totals']['launches']} launches, "
      f"{d['totals']['flops']} flops, {d['totals']['bytes']} bytes, "
      f"AI={d['totals']['arithmetic_intensity']}")
for row in d["launches"]:
    print(f"  launch[{row['start']}:{row['stop']}] gather={row['gather']}"
          f" flops={row['flops']} bytes={row['bytes']}"
          f" AI={row['arithmetic_intensity']}")

# sanity: every recorded span is a host event of the profile
missing = {r.name for r in trace.finished_spans()} - host
assert not missing, f"spans missing from the profile: {missing}"
print("\nOK — trace + report artifacts are valid")
