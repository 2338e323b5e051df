"""Validation of the while-loop-aware HLO cost model: scanned loops must
cost trip_count × the body, matching the unrolled reference that XLA's
built-in cost_analysis gets right; plus the structural backend_config
parse, the conditional max-branch rule, the all-to-all /
collective-permute byte models, and the alias/parameter helpers the
donation contract builds on."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import (analyze, collective_groups,
                                   entry_parameter_bytes,
                                   input_output_aliases,
                                   parse_backend_config,
                                   trip_count_from_config)


def _hlo(f, *args):
    return jax.jit(f).lower(*args).compile().as_text()


def test_scan_flops_match_unrolled():
    w = jnp.ones((128, 128))

    def scanned(x):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    def unrolled(x):
        for _ in range(10):
            x = x @ w
        return x

    x = jnp.ones((128, 128))
    c_scan = analyze(_hlo(scanned, x))
    c_unroll = analyze(_hlo(unrolled, x))
    base = 2 * 128 ** 3
    assert c_unroll.flops == pytest.approx(10 * base, rel=0.01)
    assert c_scan.flops == pytest.approx(10 * base, rel=0.15)


def test_xla_builtin_undercounts_scan():
    """Documents the undercount this module exists to fix."""
    w = jnp.ones((128, 128))

    def scanned(x):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    x = jnp.ones((128, 128))
    builtin = jax.jit(scanned).lower(x).compile().cost_analysis()["flops"]
    ours = analyze(_hlo(scanned, x)).flops
    assert ours > 5 * builtin


def test_nested_scan_multiplies():
    w = jnp.ones((64, 64))

    def nested(x):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            c, _ = jax.lax.scan(inner, c, None, length=4)
            return c, None
        out, _ = jax.lax.scan(outer, x, None, length=3)
        return out

    x = jnp.ones((64, 64))
    c = analyze(_hlo(nested, x))
    base = 2 * 64 ** 3
    assert c.flops == pytest.approx(12 * base, rel=0.15)


def test_bytes_scale_with_loop():
    def scanned(x):
        def body(c, _):
            return c * 2.0, None
        out, _ = jax.lax.scan(body, x, None, length=16)
        return out

    big = analyze(_hlo(scanned, jnp.ones((1024, 1024)))).bytes
    small = analyze(_hlo(scanned, jnp.ones((128, 128)))).bytes
    assert big > 20 * small


# ------------------- structural backend_config parse -----------------------

def test_parse_backend_config_inline_and_quoted():
    inline = ('while((s32[], f32[8]) %tuple), condition=%c, body=%b, '
              'backend_config={"known_trip_count":{"n":"12"},'
              '"other":{"nested":{"x":1}}}')
    quoted = ('while((s32[]) %t), body=%b, '
              'backend_config="{\\"known_trip_count\\":{\\"n\\":\\"9\\"}}"')
    assert trip_count_from_config(inline) == 12
    assert trip_count_from_config(quoted) == 9
    assert parse_backend_config(inline)["other"]["nested"]["x"] == 1
    # Absent / unparseable configs fall back to None, never raise.
    assert parse_backend_config("while(%t), body=%b") is None
    assert trip_count_from_config('backend_config="not json"') is None
    assert trip_count_from_config('backend_config={"no_trips":{}}') is None


def test_trip_count_parsed_from_real_scan_config():
    """The structural parse on a genuinely lowered scan: XLA stamps the
    while op with known_trip_count, and the parser must recover exactly
    the scan length from that attribute (not from punctuation luck)."""
    def f(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    text = _hlo(f, jnp.eye(16))
    while_lines = [ln for ln in text.splitlines() if " while(" in ln]
    assert while_lines, "no while op in compiled scan"
    assert trip_count_from_config(while_lines[0]) == 7


# ----------------------- conditional max-branch ----------------------------

def test_conditional_costs_max_branch():
    """`conditional` recurses into the heaviest branch: a switch between a
    cheap scale and three chained matmuls must cost ~the matmul branch.
    (The chain is deliberately CSE-proof: ``(x@x)@(x@x)`` would dedupe to
    two dots.)"""
    def f(i, x):
        return jax.lax.switch(
            i, [lambda x: x * 2.0, lambda x: ((x @ x) @ x) @ x], x)

    c = analyze(_hlo(f, jnp.int32(0), jnp.eye(64)))
    base = 2 * 64 ** 3
    assert c.flops == pytest.approx(3 * base, rel=0.15)


# ------------------- collective byte / moved models ------------------------

_COLL_HLO = """\
HloModule m

ENTRY %main (p0: f32[64,64]) -> f32[64,64] {
  %p0 = f32[64,64]{1,0} parameter(0)
  %a2a = f32[64,64]{1,0} all-to-all(f32[64,64]{1,0} %p0), replica_groups={{0,1,2,3}}, dimensions={0}
  ROOT %cp = f32[64,64]{1,0} collective-permute(f32[64,64]{1,0} %a2a), source_target_pairs={{0,1},{1,2}}
}
"""


def test_all_to_all_and_permute_byte_models():
    nbytes = 64 * 64 * 4
    c = analyze(_COLL_HLO)
    assert c.coll["all-to-all"]["count"] == 1
    assert c.coll["all-to-all"]["bytes"] == nbytes
    # all-to-all keeps 1/n resident: (n-1)/n of the payload moves.
    assert c.coll["all-to-all"]["moved"] == pytest.approx(nbytes * 3 / 4)
    # collective-permute is a point-to-point shift: the payload moves once.
    assert c.coll["collective-permute"]["count"] == 1
    assert c.coll["collective-permute"]["moved"] == pytest.approx(nbytes)
    groups = collective_groups(_COLL_HLO)
    by_kind = {g["kind"]: g for g in groups}
    assert by_kind["all-to-all"]["group_size"] == 4
    # No replica_groups attribute parses to None ("possibly global").
    assert by_kind["collective-permute"]["group_size"] is None


# ------------------- alias / entry-parameter helpers -----------------------

def test_aliases_and_param_bytes_on_donated_fn():
    def f(state, x):
        return state + x, x.sum()

    state = jnp.ones((256, 64))
    x = jnp.ones((256, 64))
    donated = jax.jit(f, donate_argnums=(0,)).lower(state, x)
    text = donated.compile().as_text()
    aliased = input_output_aliases(text)
    sizes = entry_parameter_bytes(text)
    assert 0 in aliased, (aliased, text.split("\n", 1)[0])
    assert sizes[0] == 256 * 64 * 4
    assert sizes[1] == 256 * 64 * 4
    # Without donation the alias entry disappears — the donation lint's
    # failure signal.
    plain = jax.jit(f).lower(state, x).compile().as_text()
    assert input_output_aliases(plain) == []
