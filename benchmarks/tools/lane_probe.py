"""What the fused epoch program holds and takes at L lanes x T steps:

    python3 benchmarks/tools/lane_probe.py <lanes> <steps> [epochs]

compiles the program apart from running it, prints its compiled memory
analysis (arguments, scratch), then runs epochs and prints each one's
seconds beside the allocator's ``peak_bytes_in_use``. The banks of 8
lanes are tiled to L so that the host build stays short (sampling 320
banks takes ~105 s); shapes and program are otherwise the cell's. This
is the probe behind ``memory_peak_bytes`` = allocator peak + scratch
(PERF.md section 3); lines also go to chiprun_out/probe_lanes.log."""
import os, sys, time, json
T0 = time.perf_counter()
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
L = int(sys.argv[1]); T = int(sys.argv[2]); N = int(sys.argv[3]) if len(sys.argv) > 3 else 3
os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
LOG = open(os.path.join(REPO, "chiprun_out", "probe_lanes.log"), "a")
def say(tag, **kw):
    line = json.dumps({"t": round(time.perf_counter() - T0, 2), "tag": tag, **kw}, default=str)
    print(line, flush=True); LOG.write(line + "\n"); LOG.flush(); os.fsync(LOG.fileno())
import tempfile
from benchmarks import harness
from benchmarks.paths import train
if os.environ.get("PROBE_CPU"):
    harness.require_chips = lambda chips: None
cache = harness.start_backend(1)
import jax, jax.numpy as jnp
say("backend", cache=cache, dev=str(jax.devices()[0]))
cell = harness.load_cell("ramp32_dev.train_fused")
cell.traffic["overrides"] = [o for o in cell.traffic["overrides"] if not any(k in o for k in ("fused_config", "num_envs", "rollout_length"))] + ["epoch_loop.fused_config={lanes: 8, segment_len: %d}" % T, "epoch_loop.num_envs=8", "epoch_loop.rollout_length=%d" % T]
with tempfile.TemporaryDirectory() as d:
    cfg = train.compose(cell, 0, d)
    loop = train._train_from_config().build_run(cfg).epoch_loop
    say("built")
    from ddls_tpu.rl import fused as fused_mod
    env0, et, ot = loop._device_tables()
    banks = jax.tree_util.tree_map(lambda x: jnp.tile(x, (L // 8,) + (1,) * (x.ndim - 1)), loop.fused._banks)
    sh_fn = getattr(loop.learner, "_state_shardings", None)
    ssh = sh_fn(loop.state) if sh_fn is not None else getattr(loop.learner, "_replicated", None)
    drv = fused_mod.FusedEpochDriver(et, ot, loop.model, banks, T, 1, train_step_fn=loop._fused_step_fn(), state_shardings=ssh, mesh=loop.mesh, memo_cfg=loop._memo_knob())
    say("driver", lanes=drv.num_lanes, seg=drv.segment_len)
    state = loop.state
    rngs = (jax.random.PRNGKey(1), jax.random.PRNGKey(2))
    if drv._repl is not None:
        rngs = jax.device_put(rngs, drv._repl)
    crng, urng = rngs
    t = time.perf_counter()
    compiled = drv._jit_epoch.lower(state, drv._state, crng, urng).compile()
    ma = compiled.memory_analysis()
    say("compiled", seconds=time.perf_counter() - t, args=ma.argument_size_in_bytes, temp=ma.temp_size_in_bytes, alias=ma.alias_size_in_bytes, out=ma.output_size_in_bytes)
    sim = drv._state
    for i in range(N):
        t = time.perf_counter()
        state, sim, crng, urng, metrics, ep = compiled(state, sim, crng, urng)
        jax.block_until_ready((state, sim))
        drv._state = sim
        ms = jax.devices()[0].memory_stats() or {}
        say("epoch", i=i, seconds=time.perf_counter() - t, peak=ms.get("peak_bytes_in_use"), in_use=ms.get("bytes_in_use"), limit=ms.get("bytes_limit"), memo={k: int(v) for k, v in (drv.memo_counters() or {}).items() if k != "hit_rate"})
    loop.close()
say("done")
