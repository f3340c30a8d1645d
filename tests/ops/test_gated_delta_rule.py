"""The chunked gated delta rule (`ops/gated_delta_rule.py`) against the per-token recurrence beside it: the same
function of its inputs, forward and gradients, whatever the chunk, the group of chunks or the row's length, and
whichever form walks a group's chunks: the plain scan, or the Pallas kernels that keep the state in VMEM
(`ops/pallas/gated_delta_state.py`, interpreted here), which are also held against the plain scan on the same arrays.

Tracing and compiling is what these tests cost, so a form's output and gradients come from ONE jitted program
(`program`, compiled without LLVM's optimization passes: the arrays are tiny and an interpreted kernel is a long
program), the recurrence's is shared by every case of its shape, the inputs are drawn by one program a shape and
the comparisons are numpy's."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from modalities_tpu.ops import gated_delta_rule as rule
from modalities_tpu.ops import tiers

B, HK, HV, DK, DV = 2, 2, 4, 16, 8
WIDE = 128  # the walk's kernels serve head sizes that fill whole lane tiles


def inputs(seq: int, g_level: float, seed: int = 0, shape=(B, HK, HV, DK, DV)):
    """q and k normalised as the mixer hands them over; `g` about `g_level` (the log of the decay a token). Numpy's draws: nothing to compile."""
    b, hk, hv, dk, dv = shape
    rng = np.random.default_rng(seed)
    normal = lambda *dims: rng.standard_normal(dims, dtype=np.float32)  # noqa: E731
    q, k = normal(b, seq, hk, dk), normal(b, seq, hk, dk)
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.float32(np.sqrt(dk))
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    g = np.float32(g_level) * np.exp(0.5 * normal(b, seq, hv))
    beta = 1 / (1 + np.exp(-normal(b, seq, hv)))
    return q, k, normal(b, seq, hv, dv), g, beta


def program(fn, arguments: int = 5):
    """`fn`'s output and the gradients of a sum over it in every argument, as ONE jitted program: `(out, grads)`."""
    def loss(*xs):
        out = fn(*xs)
        return sum(jnp.sum(jnp.sin(leaf.astype(jnp.float32))) for leaf in jax.tree.leaves(out)), out

    both = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(arguments)), has_aux=True))
    return lambda *xs: (lambda value, grads: (value[1], grads))(*both(*xs))


RECURRENT = program(rule.gated_delta_rule_recurrent)  # one jit: compiled once a shape, whatever the case


@functools.lru_cache(maxsize=None)
def chunked(chunk: int, group_chunks: int):
    """The chunked form's program for a (chunk, group) pair, shared by the cases."""
    return program(functools.partial(rule.gated_delta_rule, chunk=chunk, group_chunks=group_chunks))


def gap(a, b) -> float:
    """The largest distance of two arrays over the second's largest value."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max()) / max(float(np.abs(b).max()), 1e-6)


# the row, the decay's level, the chunk and the chunks a group: whole chunks; a row that is no multiple of the chunk (padded with
# positions that change nothing, by name in the module's docstring); a state that barely decays and one that is gone in a token;
# a state carried over four chunks inside one group, and over four groups of one and of two chunks
CASES = {"two_chunks": (128, -1.0, 64, 32), "a_row_of_200_is_padded": (200, -1.0, 64, 32), "g_near_0": (128, -1e-3, 64, 32),
         "g_near_minus_20": (128, -20.0, 64, 32), "four_chunks_one_group": (64, -0.3, 16, 32), "four_groups_of_one_chunk": (64, -0.3, 16, 1),
         "seven_chunks_in_groups_of_two": (100, -0.3, 16, 2)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunked_form_is_the_recurrence(case):
    seq, g_level, chunk, group = CASES[case]
    xs = inputs(seq, g_level)
    (got, got_grads), (want, want_grads) = chunked(chunk, group)(*xs), RECURRENT(*xs)
    assert got.shape == want.shape == (B, seq, HV, DV)
    assert gap(got, want) <= 2e-5
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        # the two gates' gradients are sums of terms that all but cancel where the decay is strong (3e-4 at g about -20): a looser hold
        assert gap(a, b) <= (5e-4 if name in ("g", "beta") else 5e-5), (case, name)


def test_the_state_is_carried_from_chunk_to_chunk():
    """What a row's last chunk reads of its first: with the first chunk's keys and values wiped the last chunk's output changes
    (slow decay), and the same rows cut into two halves that each start from zero differ from the whole."""
    xs = inputs(64, -0.05)
    forward = jax.jit(functools.partial(rule.gated_delta_rule, chunk=16, group_chunks=2))
    whole = np.asarray(forward(*xs))
    halves = np.concatenate([forward(*(a[:, :32] for a in xs)), forward(*(a[:, 32:] for a in xs))], axis=1)
    np.testing.assert_allclose(whole[:, :32], halves[:, :32], atol=1e-6)
    assert np.abs(whole[:, 48:] - halves[:, 48:]).max() > 1e-3


def test_the_inverse_of_a_unit_lower_triangle_and_its_own_rule():
    rng = np.random.default_rng(1)
    lower = np.tril(rng.standard_normal((3, 16, 16), dtype=np.float32) * 0.3, k=-1)
    inverse = np.asarray(rule._unit_lower_inverse(lower))
    np.testing.assert_allclose(inverse @ (np.eye(16, dtype=np.float32) + lower), np.broadcast_to(np.eye(16), lower.shape), atol=1e-5)
    weigh = rng.standard_normal(lower.shape, dtype=np.float32)
    got = jax.grad(lambda l: jnp.sum(rule._unit_lower_inverse(l) * weigh))(lower)
    want = jax.grad(lambda l: jnp.sum(jnp.linalg.inv(jnp.eye(16) + l) * weigh))(lower)
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_value_heads_must_share_the_key_heads_evenly():
    q, k, v, g, beta = inputs(16, -1.0)
    with pytest.raises(ValueError, match="do not share"):
        rule.gated_delta_rule(q, k, v[:, :, :3], g[:, :, :3], beta[:, :, :3])


def test_bfloat16_inputs_take_bfloat16_operands_and_keep_a_float32_state():
    """The products' operands are the inputs' dtype, the carried state and the inverse float32: read off the jaxpr."""
    xs = [jnp.asarray(a, jnp.bfloat16) if i < 3 else a for i, a in enumerate(inputs(128, -1.0))]
    text = str(jax.make_jaxpr(rule.gated_delta_rule)(*xs))
    assert "f32[2,2,2,16,8]" in text  # the state [B, Hk, r, d_k, d_v]
    assert jax.eval_shape(rule.gated_delta_rule, *xs).dtype == jnp.bfloat16
    assert rule.state_bytes(16384, 32, 128, 128) == 8 * 32 * 128 * 128 * 4  # a state a group of 32 chunks and a head


# ---------------------------------------------------------------- the rule's own backward (PR 48)


def autodiff_rule(q, k, v, g, beta, *, chunk, group_chunks):
    """The form `gated_delta_rule` had before it got a backward of its own: the outer scan over rematerialized groups, left to autodiff."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    groups, chunks_a_group = rule.groups_of(s, chunk, group_chunks)
    per_group = chunks_a_group * chunk
    pad = groups * per_group - s
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (g, beta))
    by_group = lambda a: jnp.moveaxis(a.reshape(b, groups, per_group, *a.shape[2:]), 1, 0)  # noqa: E731
    one = jax.checkpoint(lambda state, xs: rule._group(state, *xs, chunk))
    _, out = jax.lax.scan(one, jnp.zeros((b, hk, hv // hk, dk, dv), jnp.float32), tuple(by_group(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1).reshape(b, groups * per_group, hv, dv)[:, :s]


# the row, the chunk and the chunks a group; with the kernels in, heads of 128 x 128 and a chunk of whole sublane tiles (16 in bfloat16)
OWN_BACKWARD = {"one_group": (64, 16, 32), "four_groups_of_two_chunks": (128, 16, 2), "a_row_of_100_is_padded": (100, 16, 2)}
OWN_BACKWARD_CASES = [(layout, dtype, False) for layout in sorted(OWN_BACKWARD) for dtype in ("float32", "bfloat16")] + [
    ("one_group", "float32", True), ("four_groups_of_two_chunks", "bfloat16", True), ("a_row_of_100_is_padded", "float32", True)]


@pytest.mark.parametrize("layout, dtype, interpreted", OWN_BACKWARD_CASES, ids=lambda value: {True: "kernels_interpreted", False: "plain_walk"}.get(value, value))
def test_the_rules_own_backward_is_autodiff_of_the_scan_over_groups(layout, dtype, interpreted):
    """Output and all five gradients: the same arithmetic in the same order, so float32 agrees to rounding and bfloat16 to the file's hold."""
    import contextlib

    seq, chunk, group = OWN_BACKWARD[layout]
    xs = inputs(seq, -0.3, shape=(1, 1, 2, WIDE, WIDE) if interpreted else (B, HK, HV, DK, DV))
    xs = [jnp.asarray(a, dtype) if i < 3 else a for i, a in enumerate(xs)]  # the gates stay float32, as the mixer hands them over
    with tiers.interpreted_kernels() if interpreted else contextlib.nullcontext():
        got, got_grads = program(functools.partial(rule.gated_delta_rule, chunk=chunk, group_chunks=group))(*xs)
        want, want_grads = program(functools.partial(autodiff_rule, chunk=chunk, group_chunks=group))(*xs)
    hold = 1e-5 if dtype == "float32" else 2e-2
    assert got.shape == want.shape == xs[2].shape and got.dtype == want.dtype and gap(got, want) <= hold
    for name, x, a, b in zip("q k v g beta".split(), xs, got_grads, want_grads):
        assert a.shape == b.shape == x.shape and a.dtype == b.dtype == x.dtype and gap(a, b) <= hold, (name, gap(a, b))


def programs(jaxpr, found: list):
    """Every equation of a program and of the programs nested in it."""
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    programs(inner, found)
    return found


@pytest.mark.parametrize("interpreted", [False, True], ids=["plain_walk", "kernels_interpreted"])
def test_a_block_that_keeps_o_and_the_group_states_computes_a_group_twice_a_step_and_not_three_times(interpreted):
    """Traced, not run. A group's `intra` (the inverse's series among its products) stands in the forward pass and in the rule's backward,
    which computes a group again from the state that came into it; a rematerialized block whose policy does not list the two names holds it
    a third time, in its recomputed forward. With the kernels in, the forward kernel is counted the same way."""
    import contextlib

    xs = inputs(64, -0.3, shape=(1, 1, 2, WIDE, WIDE) if interpreted else (B, HK, HV, DK, DV))
    (b, _, hk, dk), (hv, dv) = xs[0].shape, xs[2].shape[2:]
    forward = functools.partial(rule.gated_delta_rule, chunk=16, group_chunks=2)
    loss = lambda *xs: jnp.sum(jnp.sin(forward(*xs)))  # noqa: E731

    def counted(fn):
        with tiers.interpreted_kernels() if interpreted else contextlib.nullcontext():
            eqns = programs(jax.make_jaxpr(fn)(*xs).jaxpr, [])
        products = sum(e.primitive.name == "dot_general" and "intra" in str(e.source_info.name_stack) and "transpose" not in str(e.source_info.name_stack) for e in eqns)
        kernels = sum(e.primitive.name == "pallas_call" and e.params["name"] == "gated_delta_state_fwd" for e in eqns)
        names = {e.params["name"]: e.outvars[0].aval for e in eqns if e.primitive.name == "name"}
        return products, kernels, names

    once, kernel_once, _ = counted(forward)
    assert once > 0 and kernel_once == int(interpreted)
    keeping = jax.checkpoint_policies.save_only_these_names(rule.KEPT_OUT, rule.KEPT_STATES)
    another = jax.checkpoint_policies.save_only_these_names("flash_out", "flash_lse")
    grad = lambda policy: jax.value_and_grad(jax.checkpoint(loss, policy=policy), argnums=(0, 1, 2, 3, 4))  # noqa: E731
    for policy, times in ((None, 3), (another, 3), (keeping, 2)):
        products, kernels, names = counted(grad(policy))
        assert products == times * once and kernels == times * kernel_once, (times, products, once)
        assert names[rule.KEPT_OUT].shape == xs[2].shape and names[rule.KEPT_STATES].shape == (2, b, hk, hv // hk, dk, dv)  # a state a group
        assert names[rule.KEPT_STATES].dtype == jnp.float32
    assert counted(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))[:2] == (2 * once, 2 * kernel_once)  # no rematerialized block: forward, and the rule's backward


# ---------------------------------------------------------------- the walk's kernels (interpreted: `tiers.interpreted_kernels()`)



def prepared(dtype, chunks=3, key_heads=1, r=2, chunk=16, seed=0):
    """What `_group` hands the walk: the state that came in, u, w, within, q_in, k_out, carry_decay."""
    rng = np.random.default_rng(seed)
    lead = (chunks, 1, key_heads, r, chunk)
    normal = lambda *last, scale=0.3: jnp.asarray(scale * rng.standard_normal((*lead, *last), dtype=np.float32), dtype)  # noqa: E731
    within = jnp.asarray(np.tril(0.3 * rng.standard_normal((*lead, chunk), dtype=np.float32)), dtype)
    return (rng.standard_normal((1, key_heads, r, WIDE, WIDE), dtype=np.float32), normal(WIDE, scale=1.0), normal(WIDE), within, normal(WIDE), normal(WIDE),
            1 / (1 + np.exp(-rng.standard_normal(lead[:4], dtype=np.float32))))


# float32 products are exact in both forms; bfloat16 ones round the same operands, and the kernels sum the backward's
# cotangents in float32 where autodiff rounds each to the operand's dtype first: within one rounding of bfloat16 (2^-8) a term
@pytest.mark.parametrize("dtype, hold", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)], ids=["float32", "bfloat16"])
def test_the_kernels_walk_is_the_plain_walk_outputs_and_every_gradient(dtype, hold):
    xs = prepared(dtype)
    with tiers.interpreted_kernels():
        assert "gated_delta_state_fwd" in str(jax.make_jaxpr(rule._walk)(*xs))
        got, got_grads = program(rule._walk, 7)(*xs)
    want, want_grads = program(rule._plain_walk, 7)(*xs)
    for name, a, b in zip(("state", "o"), got, want):
        assert a.dtype == b.dtype and gap(a, b) <= (0.0 if dtype == jnp.bfloat16 else hold), name  # the forward rounds where the scan does: the same numbers
    for name, a, b in zip("state u w within q_in k_out carry_decay".split(), got_grads, want_grads):
        assert a.dtype == b.dtype and a.shape == b.shape and gap(a, b) <= hold, (name, gap(a, b))


# sequence, key heads, value heads, chunk, chunks a group: one group; several groups (the state handed from one kernel call to the
# next, its cotangent back); one value head a key head; a row padded to whole chunks
KERNEL_CASES = {"one_group": (32, 1, 2, 8, 32), "four_chunks_in_groups_of_two": (32, 1, 2, 8, 2), "one_value_head_a_key_head": (32, 2, 2, 8, 2),
                "a_row_of_28_is_padded": (28, 1, 2, 8, 2)}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_chunked_form_with_the_kernels_in_is_the_recurrence(case):
    seq, hk, hv, chunk, group = KERNEL_CASES[case]
    xs = inputs(seq, -0.3, shape=(1, hk, hv, WIDE, WIDE))
    with tiers.interpreted_kernels():
        got, got_grads = program(functools.partial(rule.gated_delta_rule, chunk=chunk, group_chunks=group))(*xs)
    want, want_grads = RECURRENT(*xs)
    assert got.shape == want.shape and gap(got, want) <= 2e-5
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        assert gap(a, b) <= 5e-5, (case, name)


def test_under_a_mesh_the_kernels_run_per_shard_of_batch_and_heads():
    """dp_shard 2 x tp 2 on the CPU's virtual devices: the walk goes through `per_shard` (a `shard_map` over both axes, one
    sequence and one key head a shard), and output and gradients are still the recurrence's."""
    from modalities_tpu.parallel.sharding import activation_rules, default_logical_axis_rules
    from modalities_tpu.running_env.device_mesh import get_device_mesh

    handle = get_device_mesh(device_type="cpu", world_size=4, data_parallel_shard_degree=2, tensor_parallel_degree=2)
    xs = inputs(32, -0.3, shape=(2, 2, 4, WIDE, WIDE))
    fn = functools.partial(rule.gated_delta_rule, chunk=8, group_chunks=2)
    with handle.mesh, activation_rules(default_logical_axis_rules(handle), handle.mesh), tiers.interpreted_kernels():
        text = str(jax.make_jaxpr(fn)(*xs))
        assert "shard_map" in text and "gated_delta_state_fwd" in text
        got, got_grads = program(fn)(*xs)
    want, want_grads = RECURRENT(*xs)
    assert gap(got, want) <= 2e-5
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        assert gap(a, b) <= 5e-5, name


# value heads a key head, chunks a group, chunk, d_k, d_v, dtype -> key heads a grid step of 16 a shard holds (0: the plain walk)
PLANS = {"the_cells_shapes": ((2, 32, 64, 128, 128, jnp.bfloat16), 4), "float32_in_chunks_of_8": ((2, 32, 8, 128, 128, jnp.float32), 4),
         "four_value_heads_a_key_head": ((4, 32, 64, 128, 256, jnp.bfloat16), 1), "sixteen_value_heads_a_key_heads_states_do_not_fit": ((16, 32, 64, 128, 128, jnp.bfloat16), 0),
         "a_group_of_128_chunks_keeps_fewer_heads_states": ((2, 128, 64, 128, 128, jnp.bfloat16), 2),
         "d_k_64": ((2, 32, 64, 64, 128, jnp.bfloat16), 0), "d_v_192": ((2, 32, 64, 128, 192, jnp.bfloat16), 0),
         "a_bfloat16_chunk_of_8": ((2, 32, 8, 128, 128, jnp.bfloat16), 0), "a_float32_chunk_of_4": ((2, 32, 4, 128, 128, jnp.float32), 0),
         "a_groups_states_too_large_for_vmem": ((1, 32, 64, 512, 1024, jnp.bfloat16), 0)}


@pytest.mark.parametrize("case", sorted(PLANS))
def test_the_planner_serves_whole_tiles_and_the_rest_takes_the_plain_walk(case):
    from modalities_tpu.ops.pallas.gated_delta_state import plan_heads

    (r, chunks, chunk, dk, dv, dtype), heads = PLANS[case]
    assert plan_heads(16, r, chunks, chunk, dk, dv, dtype) == heads
    assert rule.walk_kernels(r, chunks, chunk, dk, dv, dtype) == ()  # off a TPU the plain walk, whatever the shapes
    with tiers.interpreted_kernels():
        assert rule.walk_kernels(r, chunks, chunk, dk, dv, dtype) == (rule.WALK_KERNELS if heads else ())
        if not heads and dk * dv <= 2**16:  # traced, not run: the refused shape holds no kernel and the scan
            shape = lambda *last: jax.ShapeDtypeStruct((chunks, 1, 2, r, chunk, *last), dtype)  # noqa: E731
            text = str(jax.make_jaxpr(rule._walk)(jax.ShapeDtypeStruct((1, 2, r, dk, dv), jnp.float32), shape(dv), shape(dk), shape(chunk), shape(dk), shape(dk),
                                                  jax.ShapeDtypeStruct((chunks, 1, 2, r), jnp.float32)))
            assert "pallas_call" not in text and "scan" in text
