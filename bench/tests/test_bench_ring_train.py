"""The four-chip training cell at smoke size on four virtual CPU devices:
the program's sharded step (weights and AdamW moments split on the
``model`` axis, the residual sequence-sharded, attention on the ppermute
ring) against the same step unsharded and against the float32 reference;
the family's placement; the host mesh; the scopes the trace readers need;
and the faults the cell's check must catch.

Everything runs in one subprocess, because the device count is fixed
before JAX starts (``tests/test_distributed.py`` does the same); the
tests read its printed readings.  The smoke model keeps qk-norm and GQA
(4 query heads, 2 kv heads) and trains 1 x 4096 tokens, so each of the 4
devices holds a 1024-token shard and the ring forced on (the XLA fold;
the Pallas fold is compiled for a v5e by ``tests/test_tpu_compile.py``).
"""
import json
import math
import os
import subprocess
import sys

import pytest

import smoke_root

BENCH = smoke_root.BENCH
ROOT = os.path.dirname(BENCH)
CELL = "qwen3-ring-smoke.smoke-ring-train"

SMOKE_RING = {
    "arch_id": "qwen3-4b", "family": "qwen3_ring", "program_preset": "smoke",
    "source": "https://huggingface.co/Qwen/Qwen3-4B/blob/main/config.json",
    "config": {**smoke_root.SMOKE_CONFIG["config"]},
    "dtype": "float32", "token_vocab": 250,
    "deployment": {"chips": 4},
}
with open(os.path.join(BENCH, "traffic", "ring-train.json")) as _f:
    RING_TRAFFIC = json.load(_f)
SMOKE_RING_TRAFFIC = {
    **smoke_root.SMOKE_TRAFFIC["smoke-train"], "batch": 1, "seq_len": 4096,
    # the cell's own check and limits
    "check": RING_TRAFFIC["check"],
}

SCRIPT = r"""
import json, re, sys, time
sys.path.insert(0, BENCH)
import jax, jax.numpy as jnp, numpy as np
import harness, training, train_controls, run as bench_run
from repro import training as program
from repro.launch.mesh import make_host_mesh
from repro.optim import adamw_init
from repro.parallel.sharding import param_specs

out = {}
out["mesh_sizes"] = [make_host_mesh(n).devices.size for n in (1, 2, 4)]
try:
    make_host_mesh(5)
    out["mesh_5"] = "made"
except ValueError:
    out["mesh_5"] = "refused"

cell = harness.Cell(CELL, ROOT)
fam = cell.family()
bundle = fam.program_bundle(cell.config)
params = fam.make_params(bundle, harness.jax_key(SEED))
specs = param_specs(bundle.kind, bundle.abstract_params(), bundle.mesh)
placed = jax.tree.map(lambda a, s: a.sharding.spec == s
                      and a.sharding.mesh.devices.size == 4, params, specs,
                      is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
out["placed"] = all(jax.tree.leaves(placed))
out["split_leaves"] = sum(1 for s in jax.tree.leaves(
    specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    if "model" in tuple(s))
opt = jax.jit(adamw_init)(params)
out["moments_placed"] = all(jax.tree.leaves(jax.tree.map(
    lambda m, p: m.sharding.is_equivalent_to(p.sharding, m.ndim),
    opt["mu"], params)))

# the scopes the trace readers find, in the sharded step's op metadata
hyper = program.TrainHyper(optimizer=training._opt_config(cell.traffic),
                           z_weight=cell.traffic["loss"]["z_weight"])
step = jax.jit(program.make_train_step(bundle.forward, hyper))
batch = training.data_fn(cell.config, cell.traffic, SEED)
text = step.lower(params, opt, batch(1)).as_text(debug_info=True)
words = set()
for loc in re.findall(r'loc\("([^"]*/[^"]*)"', text):
    for part in loc.split("/")[:-1]:
        words.update(re.findall(r"[A-Za-z_][\w.\-]*", part))
out["scopes"] = sorted(words & {"qkv", "attention", "mlp", "lm_head",
                                "loss", "adamw", "ring_hop"})
del params, opt

# one step's loss and first gradient, sharded and unsharded
unsharded = bundle.on_mesh(None)
p = fam.make_params(bundle, harness.jax_key(SEED))
b1 = batch(1)
grad = lambda b: jax.jit(jax.value_and_grad(  # noqa: E731
    lambda p, x: program.step.loss_fn(b.forward, p, x)[0]))
l_s, g_s = grad(bundle)(p, b1)
host = jax.device_get(p)
l_u, g_u = grad(unsharded)(host, jax.device_get(b1))
norms = lambda t: {  # noqa: E731
    jax.tree_util.keystr(k): float(np.linalg.norm(np.asarray(v, np.float64)))
    for k, v in jax.tree_util.tree_flatten_with_path(t)[0]}
out["loss_sharded"], out["loss_unsharded"] = float(l_s), float(l_u)
gs, gu = norms(g_s), norms(g_u)
out["grad_rel"] = max(abs(gs[k] - gu[k]) / gu[k] for k in gu)
del p, g_s, g_u, host

# the sound cell through the harness, and its readings against the
# reference's
res, ok = bench_run.execute(cell, SEED, 1.0, False, jax.devices()[:4],
                            time.monotonic())
out["sound"] = {"correct": ok, "checks": res["checks"],
                "attempted": res["attempted"]}

out["gaps"] = train_controls.run(cell, SEED)
limits = cell.traffic["check"]["limits"]
out["caught"] = {k: train_controls.caught(g, limits)
                 for k, g in out["gaps"].items()}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    root = smoke_root.make(str(tmp_path_factory.mktemp("ring")))
    with open(os.path.join(root, "bench", "configs",
                           "qwen3-ring-smoke.json"), "w") as f:
        json.dump(SMOKE_RING, f)
    with open(os.path.join(root, "bench", "traffic",
                           "smoke-ring-train.json"), "w") as f:
        json.dump(SMOKE_RING_TRAFFIC, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "qwen3-ring-smoke", "source": "smoke",
                             "file": "bench/configs/qwen3-ring-smoke.json",
                             "reduced": [], "why": "CPU tests"})
    bench["workloads"].append({"name": CELL, "config": "qwen3-ring-smoke",
                               "traffic": "smoke-ring-train", "chips": 4,
                               "why": "CPU tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "qwen3-4b-16l.ring-train" in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_RING_ATTN="ring",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    code = (f"BENCH = {BENCH!r}\nROOT = {root!r}\nCELL = {CELL!r}\n"
            f"SEED = {2**31 + 11}\n" + SCRIPT)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-6000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_host_mesh_spans_the_devices_asked_for(readings):
    assert readings["mesh_sizes"] == [1, 2, 4]
    assert readings["mesh_5"] == "refused"


def test_family_places_weights_and_moments_by_param_specs(readings):
    assert readings["placed"] and readings["moments_placed"]
    assert readings["split_leaves"] >= 9    # 7 layer matrices, embed, head


def test_sharded_step_carries_the_readers_scopes(readings):
    assert readings["scopes"] == sorted(
        ["qkv", "attention", "mlp", "lm_head", "loss", "adamw", "ring_hop"])


@pytest.mark.parametrize("against", ["unsharded", "reference"])
def test_sharded_step_matches(readings, against):
    """One sharded step against the same step on one device: the same
    float32 arithmetic in another order (ring fold, partial sums across
    chips), so the loss agrees to 1e-5 relative and each leaf's gradient
    norm to 1e-4.  Three sharded steps against the float32 reference
    (which rounds every product at ``highest``, the program at the CPU's
    float32): each gap far inside the cell's limits (0.01, 0.004), at
    1e-3."""
    if against == "unsharded":
        a, b = readings["loss_sharded"], readings["loss_unsharded"]
        assert abs(a - b) <= 1e-5 * abs(b), (a, b)
        assert readings["grad_rel"] < 1e-4, readings["grad_rel"]
    else:
        g = readings["gaps"]["sound"]
        assert g["loss_gap"] < 1e-3, g
        assert g["grad_norm_gap"] < 1e-3 and g["update_norm_gap"] < 1e-3, g


def test_sound_run_through_the_harness_is_correct(readings):
    s = readings["sound"]
    assert s["correct"] and s["attempted"] > 0
    assert all(math.isfinite(c["value"]) for c in s["checks"].values())


@pytest.mark.parametrize("fault", ["half_sequence", "skip_last_hop",
                                   "bf16"])
def test_controls_fail_a_limit(readings, fault):
    """A loss over half the sequence's tokens, a ring that skips its last
    hop (the last chip's queries lose the first shard's keys), and the
    reference in bfloat16 each fail the cell's check, as the harness
    decides ``correct``; the sound step passes it."""
    assert readings["caught"][fault], readings["gaps"][fault]
    assert not readings["caught"]["sound"], readings["gaps"]["sound"]


# ---------------------------------------------------------------------------
# the cell's per-layer readers, on a hand-made two-chip trace
# ---------------------------------------------------------------------------

RING_CELL = "qwen3-4b-16l.ring-train"
MS = 1_000_000      # ns
# each chip's ops in one traced 10 ms step: (instruction, start, end)
OPS = (("%fold.1 = f32[8] custom-call()", 0, 4 * MS),
       ("%collective-permute-done.2 = f32[8] x()", 4 * MS, 5 * MS),
       ("%all-gather-start.3 = f32[8] y()", 5 * MS, 5 * MS + MS // 2),
       ("%fusion.4 = f32[8] fusion()", 6 * MS, 7 * MS + MS // 2),
       # the TPU compiler's async form of a matmul's collective, as named
       # in a v5e trace of the cell
       ("%async-collective-done.5 = f32[8] z()", 7 * MS + MS // 2, 8 * MS))
SCOPED = ("jit(train_step)/attention/shard_map/while/body/ring_hop/"
          "pallas_call",
          "jit(train_step)/transpose(jvp(attention))/shard_map/ring_hop/"
          "ppermute",
          "jit(train_step)/qkv/all_gather",
          "jit(train_step)/mlp/dot_general",
          "jit(train_step)/mlp/dot_general")


def _read(metric, scoped=True):
    """``metric``'s reading of two chips that each run OPS, the fold and
    the permute under ``ring_hop`` (or under no scope at all)."""
    import harness
    import program_trace
    ops = {f"/device:TPU:{d}": list(OPS) for d in (0, 1)}
    framework = dict(zip((o[0] for o in OPS), SCOPED)) if scoped else {}
    trace = program_trace.ProgramTrace(ops, {}, [("train_step", 0, 10 * MS)],
                                       framework)
    cell = harness.Cell(RING_CELL)
    ctx = {"program_trace": trace, "xplane": None,
           "reduction": {"busy_s": 7.5e-3, "window_s": 10e-3},
           "steps_traced": [(0.0, 0.010)], "tokens_per_step": 8192,
           "config": cell.config, "family": cell.family(),
           "traffic": cell.traffic, "peaks": harness.peaks_for("TPU v5 lite")}
    return harness.layer_reader(metric).read(ctx)


def test_ring_train_readers_on_a_trace():
    import harness
    cell = harness.Cell(RING_CELL)
    fam, cfg = cell.family(), cell.config
    # 14 Dh FLOPs a tile entry; 16 layers x 4 hops x 32 heads x 2048^2
    flops, nbytes = fam.ring_hop_cost(cfg, 1, 8192, 4)
    assert flops == 14 * 128 * 16 * 4 * 32 * 2048 ** 2
    least = max(flops / 197e12, nbytes / 819e9)
    assert math.isclose(_read("ring_hop_roofline.ring-train"),
                        100 * least / 5e-3)
    assert math.isclose(_read("exposed_collective_share.ring-train"),
                        100 * 2.0 / 7.5)
    assert math.isclose(_read("attention_share.ring-train"), 100 * 5 / 7.5)
    assert math.isclose(_read("idle_share.ring-train"), 25.0)
    fpt = fam.train_flops_per_token(cfg, 8192)
    assert math.isclose(_read("mfu.ring-train"),
                        100 * fpt * 8192 / (0.010 * 4 * 197e12))


def test_ring_train_readers_find_nothing_in_an_unscoped_program():
    """A program whose ops carry no ``ring_hop`` or ``attention`` scope
    (the parent of this cell) reads None there, and raises nothing."""
    assert _read("ring_hop_roofline.ring-train", scoped=False) is None
    assert _read("attention_share.ring-train", scoped=False) is None
