"""``core.sharding.kernel_call`` on a multi-device mesh, in interpret mode.

Every Pallas call the models make goes through ``kernel_call``, which on
a mesh of more than one device wraps it in ``shard_map`` split over the
batch axes. Each case below computes the same kernel call twice in one
process with 8 forced host devices on a (4, 2) ("data", "model") mesh:
once plainly, once through ``kernel_call`` under a ShardCtx with inputs
placed as the engines place them (the paged pool sharded over ``model``)
— values and gradients must agree. The session itself owns a one-device
jax, so the cases run in one subprocess shared by the module.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os, sys, json, functools
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
sys.path.insert(0, %r)
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import ParallelConfig
from repro.core.precision import QuantPolicy, quant_linear
from repro.core.sharding import ShardCtx, kernel_call
from repro.kernels.flash_attention import ops as FA
from repro.kernels.paged_attention import ops as PA
from repro.models.params import default_rules

BK = "pallas_interpret"
mesh = jax.make_mesh((4, 2), ("data", "model"))
ctx = ShardCtx(mesh, default_rules(ParallelConfig(
    mesh_shape=(4, 2), mesh_axes=("data", "model"))))
B, S, H, KV, hd, bs, nb = 8, 32, 4, 2, 32, 8, 4
N = B * nb
rng = np.random.default_rng(0)
f32, bf16 = jnp.float32, jnp.bfloat16


def arr(shape, dtype=f32, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape) * scale, dtype)


def place(x, *spec):
    return jax.device_put(x, NamedSharding(mesh, P(*spec)))


def both(fn, args, placed):
    # fn(call, *args): call(kernel, *kernel_args, batch_dims=...) is the
    # kernel invocation inside fn; plain vs kernel_call under the ctx
    plain = jax.jit(lambda *a: fn(
        lambda k, *ka, batch_dims: k(*ka), *a))(*args)
    def sharded(*a):
        with ctx:
            return fn(functools.partial(kernel_call, backend=BK), *a)
    with jax.set_mesh(mesh):
        got = jax.jit(sharded)(*placed)
    # per output leaf: max |sharded - plain| over max |plain|
    rel = jax.tree.map(
        lambda x, y: float(jnp.max(jnp.abs(x.astype(f32) - y.astype(f32)))
                           / jnp.max(jnp.abs(x.astype(f32)))), plain, got)
    return max(jax.tree.leaves(rel))


out = {}
q, k, v = arr((B, S, H, hd), bf16), arr((B, S, KV, hd), bf16), \
    arr((B, S, KV, hd), bf16)
do = arr((B, S, H, hd))
flash = functools.partial(FA.flash_attention, causal=True, backend=BK)


def flash_fn(call, q, k, v):
    def loss(q, k, v):
        o = call(flash, q, k, v, batch_dims=(0, 0, 0))
        return jnp.sum(o.astype(f32) * do), o
    (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                   has_aux=True)(q, k, v)
    return o, g


out["flash_fwd_bwd"] = both(
    flash_fn, (q, k, v),
    tuple(place(x, "data") for x in (q, k, v)))

kc, vc = arr((B, KV, S, hd), bf16), arr((B, KV, S, hd), bf16)
lens = jnp.asarray(rng.integers(1, S + 1, B), jnp.int32)
q1 = arr((B, 1, H, hd), bf16)
dec = functools.partial(FA.decode_attention, backend=BK)
out["flash_decode"] = both(
    lambda call, *a: call(dec, *a, batch_dims=(0, 0, 0, 0)),
    (q1, kc, vc, lens),
    (place(q1, "data"), place(kc, "data", "model"),
     place(vc, "data", "model"), place(lens, "data")))

kp, vp = arr((N + 1, KV, bs, hd), bf16), arr((N + 1, KV, bs, hd), bf16)
tables = jnp.asarray(rng.permutation(N).reshape(B, nb), jnp.int32)
pool_placed = (place(kp, None, "model"), place(vp, None, "model"),
               place(tables, "data"))
pdec = functools.partial(PA.paged_decode_attention, backend=BK)
out["paged_decode"] = both(
    lambda call, *a: call(pdec, *a, batch_dims=(0, None, None, 0, 0)),
    (q1, kp, vp, tables, lens),
    (place(q1, "data"),) + pool_placed + (place(lens, "data"),))

Sq = 8
qp = arr((B, Sq, H, hd), bf16)
q_off = jnp.asarray(rng.integers(0, nb * bs - Sq + 1, B), jnp.int32)
kv_len = q_off + Sq
ppre = functools.partial(PA.paged_prefill_attention, backend=BK)
out["paged_prefill"] = both(
    lambda call, *a: call(ppre, *a, batch_dims=(0, None, None, 0, 0, 0)),
    (qp, kp, vp, tables, q_off, kv_len),
    (place(qp, "data"),) + pool_placed
    + (place(q_off, "data"), place(kv_len, "data")))

x, w, b = arr((B, S, 64), bf16), arr((64, 96), scale=0.1), arr((96,))
# small integers: the bias gradient (a bf16 sum over all rows) is exact
# in any order, and int8 row quantization of dy is lossless
dy = jnp.asarray(rng.integers(-1, 2, (B, S, 96)), f32)
pol = QuantPolicy("int8_switchback", backend=BK)


def linear_fn(call, x, w, b):
    # quant_linear makes its own kernel_call: the plain side runs it
    # outside any ShardCtx, the sharded side inside
    def loss(x, w, b):
        y = quant_linear(x, w, b, policy=pol)
        return jnp.sum(y.astype(f32) * dy), y
    (_, y), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                   has_aux=True)(x, w, b)
    return y, g


out["switchback_linear"] = both(linear_fn, (x, w, b),
                                (place(x, "data"), place(w), place(b)))

with ctx:
    try:
        kernel_call(flash, q[:6], k[:6], v[:6], batch_dims=(0, 0, 0),
                    backend=BK)
        out["indivisible_raises"] = "no error"
    except ValueError as e:
        out["indivisible_raises"] = str(e)
    ident = lambda a: a * 2
    out["xla_plain"] = "shard_map" not in str(jax.make_jaxpr(
        lambda a: kernel_call(ident, a, batch_dims=(0,), backend="xla"))(q))
    out["pallas_wrapped"] = "shard_map" in str(jax.make_jaxpr(
        lambda a: kernel_call(ident, a, batch_dims=(0,), backend=BK))(q))
print("RESULT " + json.dumps(out))
""" % SRC


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    line = [ln for ln in run.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


# Per output leaf, max |sharded - plain| relative to the leaf's largest
# value. The two programs are compiled apart, so XLA may fuse the code
# around the kernels (the flash backward's sum(o * do), SwitchBack's
# per-device partial weight gradients added by the cotangent psum)
# differently and round a bf16 element one ulp apart: at most 2^-8 of
# that element, so under 2^-8 of the leaf's largest. A lost or doubled
# cotangent psum, a wrong split or a gathered operand read at the wrong
# offset is off by O(1) of the leaf.
TOL = 2.0 ** -8
CASES = ["flash_fwd_bwd", "flash_decode", "paged_decode", "paged_prefill",
         "switchback_linear"]


@pytest.mark.parametrize("case", CASES)
def test_kernel_call_matches_plain_call(case, results):
    assert results[case] <= TOL, (case, results[case])


def test_kernel_call_rejects_indivisible_batch(results):
    assert "do not divide" in results["indivisible_raises"]


@pytest.mark.parametrize("case", ["xla_plain", "pallas_wrapped"])
def test_kernel_call_wraps_only_pallas(case, results):
    assert results[case] is True
