"""The dropless expert layer (parallel/expert_parallel.dropless_experts)
against a loop over rows and experts, in both of its forms: every held expert
on every row in one batched product (few rows: the decode tick), and rows
sorted by expert through grouped products (many: the admission).

What is pinned: the sum itself; that no row is dropped however uneven the
routing (every row onto one expert); that a row's output does not depend on
who shares its batch (the property the capacity-routed layer of the same
file lacks, which is why the decode pools refuse that one); that the parts
the shares ``(first, count)`` give add up to the whole layer as the plain
reference (perfbench/reference_smallthinker.py, a loop over experts under
the rows' weights, no sort) gives it; the count of experts hit by live rows.

Float32 throughout: TIGHT is a few units in the last place of sums of a
few dozen products of magnitude about 1.

Reference anchor: none in the reference (SURVEY.md section 2.7: no
mixture of experts in 2016); provenance is Gale et al., "MegaBlocks"
(dropless routing as grouped products over sorted rows).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.parallel import expert_parallel as ep
from perfbench import reference_smallthinker as ref

TIGHT = 3e-6
T, D, E, F, K = 23, 16, 8, 12, 2


@pytest.fixture(scope="module")
def layer():
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    return {"x": jax.random.normal(k[0], (T, D)),
            "logits": jax.random.normal(k[1], (T, E)),
            "w_in": jax.random.normal(k[2], (E, D, 2 * F)) * 0.3,
            "w_down": jax.random.normal(k[3], (E, F, D)) * 0.3}


@pytest.fixture(params=["every_expert_on_every_row", "rows_grouped"])
def form(request, monkeypatch):
    """Both forms at one size: the rows up to which the batched form is
    taken is a constant of the module."""
    if request.param == "rows_grouped":
        monkeypatch.setattr(ep, "DENSE_ROWS", 4)
    return request.param


def _by_hand(x, logits, w_in, w_down):
    topv, topi = jax.lax.top_k(logits, K)
    w = np.asarray(jax.nn.softmax(topv, -1))
    y = np.zeros((x.shape[0], D))
    for t in range(x.shape[0]):
        for j in range(K):
            e = int(topi[t, j])
            gu = np.asarray(x[t] @ w_in[e])
            y[t] += w[t, j] * np.asarray(
                (np.maximum(gu[:F], 0) * gu[F:]) @ w_down[e])
    return y


def _whole(layer, **kw):
    return ep.dropless_experts(layer["x"], layer["logits"], layer["w_in"],
                               layer["w_down"], top_k=K, **kw)


def test_the_layer_is_the_sum_over_the_chosen_experts(layer, form):
    y, hit = jax.jit(lambda: _whole(layer))()
    want = _by_hand(layer["x"], layer["logits"], layer["w_in"],
                    layer["w_down"])
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(np.asarray(y), want, atol=TIGHT, rtol=0)
    assert int(hit) == len(set(np.asarray(
        jax.lax.top_k(layer["logits"], K)[1]).ravel()))


def test_no_row_is_dropped_when_every_row_takes_one_expert(layer, form):
    """The capacity router's worst case: all T rows onto experts 3 and 5."""
    logits = jnp.zeros((T, E)).at[:, 3].set(9.0).at[:, 5].set(8.0)
    skewed = dict(layer, logits=logits)
    y, hit = _whole(skewed)
    want = _by_hand(layer["x"], logits, layer["w_in"], layer["w_down"])
    np.testing.assert_allclose(np.asarray(y), want, atol=TIGHT, rtol=0)
    assert int(hit) == 2
    assert np.abs(np.asarray(y)).min(axis=1).max() > 0   # no row left out


def test_a_rows_output_is_its_own_whoever_shares_the_batch(layer, form):
    alone = np.asarray(_whole(layer)[0])
    for crowd in (7, 40):
        k = jax.random.split(jax.random.PRNGKey(crowd), 2)
        more = dict(
            layer,
            x=jnp.concatenate([layer["x"][:5],
                               jax.random.normal(k[0], (crowd, D))]),
            logits=jnp.concatenate([layer["logits"][:5],
                                    jax.random.normal(k[1], (crowd, E))]))
        together = np.asarray(_whole(more)[0])
        np.testing.assert_allclose(together[:5], alone[:5], atol=TIGHT,
                                   rtol=0)


def test_the_capacity_routed_layer_fails_that_property(layer):
    """Why the decode pools refuse it: past an expert's capacity a row is
    dropped, so the same row reads otherwise in a crowd."""
    params = {"Wg": jnp.eye(D, E) * 4.0, "W1": layer["w_in"][..., :F],
              "b1": jnp.zeros((E, F)), "W2": layer["w_down"],
              "b2": jnp.zeros((E, D))}
    x = jnp.abs(layer["x"][:6]).at[:, 0].add(3.0)   # every row -> expert 0
    alone = ep.moe_reference(params, x[:1], top_k=1)
    crowd = ep.moe_reference(params, x, top_k=1)
    assert np.abs(np.asarray(alone)).max() > 0
    assert not np.allclose(np.asarray(crowd[5]),
                           np.asarray(ep.moe_reference(params, x[5:6],
                                                       top_k=1)[0]))


def test_the_shares_add_up_to_the_uncut_references_layer(layer, form):
    """Four holders of two experts each, every one routing over all eight:
    their parts add up to what the plain reference gives for the whole
    layer (its loop over experts under masks, its own top-k)."""
    parts = [ep.dropless_experts(
        layer["x"], layer["logits"], layer["w_in"][a:a + n],
        layer["w_down"][a:a + n], top_k=K, first=a)
        for a, n in ((0, 2), (2, 2), (4, 2), (6, 2))]
    total = sum(np.asarray(y) for y, _ in parts)
    topv, topi = jax.lax.top_k(layer["logits"], K)
    weight = jnp.einsum("tke,tk->te",
                        jax.nn.one_hot(topi, E, dtype=jnp.float32),
                        jax.nn.softmax(topv, -1))
    zero = jnp.zeros((T, D), jnp.float32)
    want = ref._experts(zero, layer["x"], weight, layer["w_in"],
                        layer["w_down"], None)
    np.testing.assert_allclose(total, np.asarray(want), atol=TIGHT, rtol=0)
    # a share's part is not the whole, and its count is of its own experts
    assert np.abs(np.asarray(parts[0][0]) - np.asarray(want)).max() > 0.1
    assert sum(int(h) for _, h in parts) == int(_whole(layer)[1])


def test_hit_counts_the_experts_of_live_rows_alone(layer, form):
    live = jnp.arange(T) < 3
    chosen = set(np.asarray(jax.lax.top_k(layer["logits"][:3],
                                          K)[1]).ravel())
    assert int(_whole(layer, live=live)[1]) == len(chosen)
    assert int(_whole(layer, live=jnp.zeros((T,), bool))[1]) == 0


def test_both_forms_agree_and_the_scopes_are_in_the_program(layer,
                                                            monkeypatch):
    batched = np.asarray(_whole(layer)[0])
    lowered = jax.jit(lambda: _whole(layer, scope="tick.moe")).lower()
    text = lowered.as_text(debug_info=True)
    assert "tick.moe_route" in text and "tick.moe_experts" in text
    assert "ragged_dot" not in text
    monkeypatch.setattr(ep, "DENSE_ROWS", 4)
    grouped = np.asarray(_whole(layer)[0])
    np.testing.assert_allclose(grouped, batched, atol=TIGHT, rtol=0)
    text = jax.jit(lambda: _whole(layer, scope="admit.moe")).lower().as_text(
        debug_info=True)
    assert "admit.moe_route" in text and "admit.moe_experts" in text
    assert "ragged_dot" in text
