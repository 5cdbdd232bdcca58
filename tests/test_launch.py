"""Launch-layer tests: HLO analyzer correctness, sharding rules, and a
subprocess mini dry-run on 8 forced host devices (the in-process test
session keeps its single real device)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp

from repro.launch import hlo as hlo_lib

HLO_SAMPLE = """\
HloModule test

%body (arg: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %arg = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[8,8] get-tuple-element(%arg), index=1
  %w = f32[8,8] constant({...})
  %dot.1 = f32[8,8] dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%ni, %dot.1)
}

%addc (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

%cond (arg2: (s32[], f32[8,8])) -> pred[] {
  %arg2 = (s32[], f32[8,8]) parameter(0)
  %i2 = s32[] get-tuple-element(%arg2), index=0
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%i2, %c), direction=LT
}

ENTRY %main (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,8]) tuple(%zero, %p0)
  %while.1 = (s32[], f32[8,8]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  %ag = f32[16,8] all-gather(%p0), channel_id=1, replica_groups=[2,2]<=[4], dimensions={0}
  %ar = f32[8,8] all-reduce(%p0), channel_id=2, to_apply=%addc
  ROOT %out = f32[8,8] get-tuple-element(%while.1), index=1
}
"""


def test_hlo_analyzer_trip_counts_and_flops():
    stats = hlo_lib.analyze(HLO_SAMPLE)
    # dot inside the while body: 2*8*8*8 flops, x5 trip count
    assert stats.flops == 5 * 2 * 8 * 8 * 8


def test_hlo_analyzer_collectives():
    stats = hlo_lib.analyze(HLO_SAMPLE)
    assert stats.per_collective["all-gather"] == 16 * 8 * 4      # output bytes
    assert stats.per_collective["all-reduce"] == 2 * 8 * 8 * 4   # 2x operand
    assert stats.collective_count == {"all-gather": 1, "all-reduce": 1}


def test_hlo_analyzer_real_program():
    def f(x, ws):
        def body(c, w):
            return c @ w, ()
        y, _ = jax.lax.scan(body, x, ws)
        return y
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((7, 64, 64), jnp.float32)
    txt = jax.jit(f).lower(x, ws).compile().as_text()
    stats = hlo_lib.analyze(txt)
    assert stats.flops == 7 * 2 * 32 * 64 * 64


def test_param_spec_rules():
    from jax.sharding import PartitionSpec as P
    from repro.launch.shardings import param_spec

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 4}

    m = FakeMesh()
    assert param_spec("['attn']['wq']", (64, 64), m) == P(("data",), "model")
    assert param_spec("['attn']['wo']", (64, 64), m) == P("model", ("data",))
    assert param_spec("['embed']", (256, 64), m) == P("model", ("data",))
    # MoE expert-parallel when E divides the axis
    assert param_spec("['moe']['wi']", (8, 64, 64), m) == \
        P("model", ("data",), None)
    # ... ff-TP fallback when it does not
    assert param_spec("['moe']['wi']", (6, 64, 64), m) == \
        P(None, ("data",), "model")
    # non-divisible dims fall back to replicated
    assert param_spec("['attn']['wq']", (63, 64), m) == P(None, "model")
    assert param_spec("['norm1']['scale']", (64,), m) == P()


def test_input_shapes_cover_assignment():
    from repro.configs import INPUT_SHAPES
    s = INPUT_SHAPES
    assert s["train_4k"].seq_len == 4096 and s["train_4k"].global_batch == 256
    assert s["prefill_32k"].seq_len == 32768 and s["prefill_32k"].global_batch == 32
    assert s["decode_32k"].global_batch == 128 and s["decode_32k"].is_decode
    assert s["long_500k"].seq_len == 524288 and s["long_500k"].global_batch == 1


MINI_DRYRUN = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
import jax.numpy as jnp
from repro.configs import get_arch
from repro.configs.base import InputShape
from repro.launch import shardings as sh, steps as steps_lib
from repro.launch import hlo as hlo_lib
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_arch("qwen2-1.5b").reduced()
shape = InputShape("mini", 64, 8, "train")
hints = steps_lib.mesh_hints(mesh)
pspecs = steps_lib.params_specs(cfg, "float32")
psh = sh.params_shardings(pspecs, mesh)
step = steps_lib.make_train_step(cfg, hints=hints)
opt = jax.eval_shape(step.optimizer.init, pspecs)
osh = sh.params_shardings_like(opt, psh, mesh)
batch = steps_lib.batch_specs(cfg, shape)
bsh = sh.batch_shardings(batch, mesh)
fn = jax.jit(step, in_shardings=(psh, osh, bsh), out_shardings=(psh, osh, None))
with mesh:
    compiled = fn.lower(pspecs, opt, batch).compile()
stats = hlo_lib.analyze(compiled.as_text())
mem = compiled.memory_analysis()
print(json.dumps({"flops": stats.flops,
                  "coll": stats.collective_bytes,
                  "temp": mem.temp_size_in_bytes}))
"""


def test_mini_dryrun_subprocess():
    """Compile the reduced qwen2-1.5b train step on a 2x4 forced-device mesh:
    proves the sharding rules + hints produce a lowerable SPMD program with
    collectives, without touching the test session's device count."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", MINI_DRYRUN], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 0
    assert rec["coll"] > 0          # FSDP/TP must produce collectives


def test_fed_layout():
    from repro.launch.fedtrain import fed_layout

    class SP:
        axis_names = ("data", "model")

    class MP:
        axis_names = ("pod", "data", "model")

    assert fed_layout(SP()) == ("data", ())
    assert fed_layout(MP()) == ("pod", ("data",))
