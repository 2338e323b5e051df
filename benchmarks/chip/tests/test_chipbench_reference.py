"""The plain reference against the engine at a small size on the CPU
(Pallas kernels in interpret mode), across an evict and a restore into
another lane; and the fp8 control, which the comparison must fail."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import chipbench_tiny as tiny
from bench import cell, check, serving, weights

SEED = 2 ** 31 + 77


def test_one_layer_equals_its_slice_of_the_stacked_tree():
    specs = weights.leaves(tiny.MODEL, tiny.MEMORY)
    full = jax.jit(lambda w: weights.tree(w, tiny.MODEL, tiny.MEMORY))(
        weights.seed_words(SEED))
    for path in ("blocks/mlp/w1", "memory/gates", "lm_head"):
        stack = specs[path][0]
        n = 1 if stack is None else weights.stack_size(stack, tiny.MODEL,
                                                      tiny.MEMORY)
        for i in range(n):
            one = weights.one(SEED, path, specs[path], i)
            got = full[path] if stack is None else full[path][i]
            assert jnp.array_equal(one, got)
    other = weights.one(SEED + 1, "lm_head", specs["lm_head"])
    assert not jnp.array_equal(other, full["lm_head"])


def _serve(compute_dtype, backend="pallas-interpret"):
    """Three users over two lanes: user a's second turn waits until b's
    lane frees, so its session is evicted from lane 0 and restored into
    lane 1. Returns the samples `check.compare` takes."""
    from repro.launch.engine import Request
    spec_ = tiny.config(compute_dtype, lanes=2)
    spec_["program"]["memory"]["backend"] = backend
    eng = serving.program_engine_class(prefill_hop=False)(
        cell.program_config(spec_),
        make_params=serving.params_maker(SEED, spec_["model"],
                                         spec_["memory"]),
        lanes=2, max_len=96)
    rng = np.random.default_rng(1)
    p = lambda n: rng.integers(1, 256, n).tolist()  # noqa: E731
    reqs = [Request("a", p(6), 3), Request("b", p(9), 40),
            Request("c", p(7), 30), Request("a", p(5), 30)]
    res = eng.run(reqs)
    turns = {}
    for r in sorted(res, key=lambda r: r["id"]):
        turns.setdefault(r["user"], []).append(
            (reqs[r["id"]].prompt, r["tokens"]))
    N = spec_["memory"]["num_slots"]
    samples = []
    for user in ("a", "b"):
        sess = eng.sessions.peek(user)
        samples.append({
            "turns": turns[user],
            "positions": int(np.asarray(sess["pos"])[0]),
            "memory": [(st.memory[0, :N], st.last_access[0, :N])
                       for st in sess["mem"]],
            "kv": [(sess["cache"]["k"][i, 0, :int(np.asarray(sess["pos"])[0])],
                    sess["cache"]["v"][i, 0, :int(np.asarray(sess["pos"])[0])])
                   for i in range(spec_["model"]["num_layers"])]})
    eng.close()
    return spec_, samples


@pytest.fixture(scope="module", params=["pallas-interpret", "ref"])
def served_f32(request):
    return request.param, _serve("float32", request.param)


@pytest.fixture(scope="module")
def served_bf16():
    return _serve("bfloat16")


def test_float32_engine_matches_the_reference(served_f32):
    """At float32 the engine and the reference agree, through the
    restore, on the keys and values of every layer before the first
    memory access; on the program's jnp path also on every served token
    and on the whole memory of every group, usage included. The Pallas
    read may break an exact tie otherwise: the rows a head writes into
    slots it read as zero rows are parallel, so their cosines tie, and the
    kernel's normalisation rounds them apart in its own way; from there
    the two memories, and what they add to the residual, part."""
    backend, (spec_, samples) = served_f32
    assert samples[0]["positions"] == 6 + 3 - 1 + 5 + 30 - 1
    r = check.compare(samples, spec_["model"], spec_["memory"], SEED,
                      rows=2, max_len=96)
    assert r["served_tokens"] == 3 + 30 + 40
    assert r["kv_gap"] < 1e-5
    if backend == "ref":
        assert r["logit_gap"] < 1e-4
        assert r["top_mismatch"] == 0.0
        assert r["memory_diff"] < 1e-4
        assert r["usage_entries_differ"] == 0
    else:
        assert r["memory_norm_gap"] < 0.05


def test_bfloat16_engine_is_close_and_fp8_control_is_not(served_bf16):
    spec_, samples = served_bf16
    r = check.compare(samples, spec_["model"], spec_["memory"], SEED,
                      rows=2, max_len=96, control=True)
    assert r["control"]["gap_mean"] > 3 * r["gap_mean"]
    assert r["kv_gap"] < 0.02
    assert r["control"]["kv_gap"] > 3 * r["kv_gap"]


def test_bfloat16_share_reads_the_bits():
    from reference.model import _bf16_share
    m = jnp.array([[[1.0, 0.0, 1.0 + 2 ** -10, -3.5],
                    [0.0, 0.0, 2 ** -20, 1.0 + 2 ** -7]]], jnp.float32)
    assert float(jax.jit(_bf16_share)(m)[0]) == pytest.approx(4 / 5)
