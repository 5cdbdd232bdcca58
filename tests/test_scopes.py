"""Layer names in the round: the ``fl.*`` named scopes in the compiled
round program and the ``fl.*`` host spans that ``FederatedServer.run``
puts on the profiler's clock."""

import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import FederatedServer, StaticSampling, strategy
from repro.core.strategy import build_round

M, COHORT, NB, B, D, K = 8, 4, 2, 4, 16, 32   # w is 512 elements: masked
SCOPES = {"fl.local_update", "fl.mask", "fl.encode", "fl.decode",
          "fl.aggregate"}
CHECKED_OPS = {"sort", "scatter", "dot", "convolution", "custom-call",
               "while"}
HLO_LINE = re.compile(r"\s*(?:ROOT )?%?[\w.\-]+ = .*? ([a-z][\w\-]*)\(")


def _problem():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (M, NB, B, D))
    y = jax.random.randint(jax.random.fold_in(key, 1), (M, NB, B), 0, K)

    def loss_fn(params, data):
        xb, yb = data
        logp = jax.nn.log_softmax(xb @ params["w"] + params["b"])
        return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], 1))

    params = {"w": 0.1 * jax.random.normal(jax.random.fold_in(key, 2),
                                           (D, K)),
              "b": jnp.zeros((K,))}
    return loss_fn, params, (x, y), jnp.ones((M,), jnp.float32)


def _strategy(name):
    return strategy.get(name, sampling=StaticSampling(initial_rate=0.5,
                                                      min_clients=2))


def _innermost_scope(line):
    m = re.search(r'op_name="([^"]*)"', line)
    found = re.findall(r"fl\.[a-z_]+", m.group(1)) if m else []
    return found[-1] if found else None


@pytest.mark.parametrize("preset", ["fig5-fused", "dense-baseline"])
def test_round_program_ops_carry_layer_scopes(preset):
    """Every sort, scatter, matmul, custom call and loop of the compiled
    cohort round sits under an ``fl.`` scope; the fused top-k round has
    all five layers, the dense round no wire layers."""
    loss_fn, params, batches, n = _problem()
    fn = build_round(_strategy(preset), loss_fn, M, form="cohort",
                     cohort_size=COHORT)
    res = jax.tree.map(lambda p: jnp.zeros((M,) + p.shape, p.dtype), params)
    text = jax.jit(fn).lower(params, res, batches, n, jnp.float32(1),
                             jax.random.PRNGKey(1)).compile().as_text()
    seen, unscoped, checked = set(), [], 0
    for line in text.splitlines():
        m = HLO_LINE.match(line)
        if not m:
            continue
        scope = _innermost_scope(line)
        if scope:
            seen.add(scope)
        if m.group(1) in CHECKED_OPS:
            checked += 1
            if scope is None:
                unscoped.append(line.strip()[:200])
    assert checked > 0
    assert unscoped == []
    if preset == "fig5-fused":
        assert seen == SCOPES
    else:
        assert seen == {"fl.local_update", "fl.aggregate"}


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    spans = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name.split("#", 1)[0]
                if name.startswith("fl."):
                    spans.append((name, ev.start_ns, ev.end_ns))
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def test_server_run_host_spans(tmp_path):
    """Two rounds traced: ``fl.plan`` comes first, each ``fl.round``
    encloses ``fl.launch``, ``fl.wait`` and ``fl.readback`` in that order,
    and ``fl.compile`` shows once, inside the first round's launch."""
    loss_fn, params, batches, n = _problem()
    server = FederatedServer.from_strategy(
        _strategy("dense-baseline"), loss_fn, params, M, seed=0,
        scan_rounds=False)
    with jax.profiler.trace(str(tmp_path)):
        server.run(batches, n, 2)
    assert len(server.history) == 2
    spans = _host_spans(str(tmp_path))
    rounds = [s for s in spans if s[0] == "fl.round"]
    assert len(rounds) == 2
    plans = [s for s in spans if s[0] == "fl.plan"]
    assert len(plans) == 1 and plans[0][2] <= rounds[0][1]
    for _, lo, hi in rounds:
        inner = [s for s in spans
                 if s[0] != "fl.round" and lo <= s[1] and s[2] <= hi]
        phases = [s for s in inner if s[0] != "fl.compile"]
        assert [s[0] for s in phases] == ["fl.launch", "fl.wait",
                                          "fl.readback"]
        assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
    compiles = [s for s in spans if s[0] == "fl.compile"]
    assert len(compiles) == 1
    launch = next(s for s in spans if s[0] == "fl.launch")
    assert launch[1] <= compiles[0][1] and compiles[0][2] <= launch[2]
    assert launch[2] <= rounds[1][1]
