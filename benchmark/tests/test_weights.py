"""The seeded weights' draws (``harness/weights.py``): the vocabulary a
family's reference names a leaf's draw from, its refusals, how
``spec.family_layers`` asks for it, and why it exists: a gated-delta
layer's state under the decays today's rule gives and under the
published ones."""
import types

import numpy as np
import pytest

from harness import spec, weights

SEED = 2 ** 31 + 7
SHAPES = {"w": (64, 32), "A_log": (4096,), "dt_bias": (4096,),
          "gain": (4096,), "lo": (4096,), "u": (4096,)}


def _draw(draws, shapes=SHAPES):
    return {k: np.asarray(v, np.float64)
            for k, v in weights.make_group(SEED, 0, shapes, "float32",
                                           draws).items()}


def test_each_draw_lands_in_its_range():
    """Uniform and log-uniform, bare and through each transform, on a
    fixed seed: inside the range, and spread over it as the kind says."""
    got = _draw({"A_log": ("uniform", 0.0, 16.0, "log"),
                 "dt_bias": ("log_uniform", 0.001, 0.1, "softplus_inv"),
                 "lo": ("log_uniform", 0.001, 0.1),
                 "u": ("uniform", 1.0, 16.0)})
    a = np.exp(got["A_log"])
    assert np.all(np.isfinite(got["A_log"]))
    assert 0.0 < a.min() and a.max() < 16.0
    assert abs(a.mean() - 8.0) < 0.3
    assert 1.0 <= got["u"].min() and got["u"].max() < 16.0
    assert abs(got["u"].mean() - 8.5) < 0.3
    lo = got["lo"]
    assert 0.001 <= lo.min() and lo.max() < 0.1
    # log-uniform: the log's mean is the middle of the logs' range
    assert abs(np.log(lo).mean() - np.log(0.01)) < 0.05
    # the inverse of softplus: softplus(dt_bias) is the drawn time step
    dt = np.logaddexp(0.0, got["dt_bias"])
    assert 0.001 * (1 - 1e-5) <= dt.min() and dt.max() < 0.1 * (1 + 1e-5)
    assert abs(np.log(dt).mean() - np.log(0.01)) < 0.05


def test_a_named_leaf_takes_the_uniform_its_rule_takes():
    """Same key, same index: the gain's own range named as a draw gives
    the gain's values to rounding, and the leaves no draw names keep
    theirs bit for bit."""
    half = 0.1 * weights.SQRT3
    plain = _draw(None)
    named = _draw({"gain": ("uniform", 1.0 - half, 1.0 + half),
                   "A_log": ("uniform", 0.0, 16.0, "log")})
    np.testing.assert_allclose(named["gain"], plain["gain"], atol=1e-6)
    for k in ("w", "dt_bias", "lo", "u"):
        assert np.array_equal(named[k], plain[k]), k
    assert not np.allclose(named["A_log"], plain["A_log"])


@pytest.mark.parametrize("draws, says", [
    ({"A_log": ("normal", 0.0, 1.0)}, "kind one of"),
    ({"A_log": ("uniform", 0.0, 16.0, "exp")}, "transform one of"),
    ({"A_log": ["uniform", 0.0, 16.0]}, "a draw is"),
    ({"A_log": ("log_uniform", 0.0, 0.1)}, "lo < hi"),
    ({"A_log": ("uniform", -1.0, 16.0, "log")}, "lo < hi"),
    ({"A_log": ("uniform", 16.0, 1.0)}, "lo < hi"),
    ({"D": ("uniform", 0.0, 1.0)}, "no such leaf")])
def test_a_draw_outside_the_vocabulary_or_the_leaves_is_refused(draws,
                                                                says):
    with pytest.raises(ValueError, match=says):
        weights.check_draws(draws, SHAPES, "references/hybrid.py")


def _family(kinds=None, draws=None, asked=None):
    """A reference module reduced to what ``family_layers`` reads."""
    ref = types.SimpleNamespace(
        layer_shapes=lambda cfg, *kind: {"A_log": (2,), "w": (4, 4)})
    if kinds is not None:
        ref.layer_kinds = lambda cfg: kinds
    if draws is not None:
        def leaf_draws(cfg, *kind):
            asked.append(kind)
            return draws
        ref.leaf_draws = leaf_draws
    return ref


def test_family_without_leaf_draws_is_never_asked():
    cfg = {"num_hidden_layers": 2, "family": "plain"}
    kinds, shapes, draws = spec.family_layers(_family(), cfg)
    assert kinds == [None, None] and draws == {None: {}}


def test_leaf_draws_is_handed_the_kind_as_layer_shapes_is():
    cfg = {"num_hidden_layers": 3, "family": "hybrid"}
    published = {"A_log": ("uniform", 0.0, 16.0, "log")}
    asked = []
    _, _, draws = spec.family_layers(
        _family(draws=published, asked=asked), cfg)
    assert asked == [()] and draws == {None: published}
    asked = []
    _, _, draws = spec.family_layers(
        _family(["lin", "lin", "full"], published, asked), cfg)
    assert sorted(asked) == [("full",), ("lin",)]
    assert draws == {"lin": published, "full": published}


def test_family_naming_a_missing_leaf_is_refused_by_name():
    cfg = {"num_hidden_layers": 1, "family": "hybrid"}
    ref = _family(draws={"dt_bias": ("log_uniform", 0.001, 0.1)}, asked=[])
    with pytest.raises(ValueError, match=r"references/hybrid\.py.*"
                       r"'dt_bias'.*no such leaf"):
        spec.family_layers(ref, cfg)


# -- why a family names its draws ---------------------------------------------
# A gated-delta layer with Olmo-Hybrid-7B's 30 linear heads and hidden width
# of 3840, so that W_a x spreads as it will there (0.02 sqrt(3840) = 1.24),
# at toy key and value widths (8, 16); the short convolution before q, k and
# v reaches back 3 tokens and is left out.
H, DK, DV, HID, T, CUT = 30, 8, 16, 3840, 1100, 512
GDN = {"q": (HID, H * DK), "k": (HID, H * DK), "v": (HID, H * DV),
       "b": (HID, H), "a": (HID, H), "A_log": (H,), "dt_bias": (H,)}
# FLA's GatedDeltaNet: A from U(0, 16), dt log-uniform on [0.001, 0.1]
PUBLISHED = {"A_log": ("uniform", 0.0, 16.0, "log"),
             "dt_bias": ("log_uniform", 0.001, 0.1, "softplus_inv")}
# The carry is dropped at token 512, a chunk boundary.  Outputs from 16
# tokens after it on are compared, where today's draw (decay e^g of 0.03 a
# token at the median) has forgotten the state to float64 rounding.  The
# largest move over the largest output, on seeds 1-20, 2**31 + 7,
# 3 * 10**9 + 1 and 2**32 + 5: 0.098 to 0.33 under the published draws, at
# most 1.1e-15 under today's.  A limit of 1e-3 lies a hundred times under
# the least published reading, and ten thousand times over float32's
# rounding of the carried state, so a float32 comparison sees a move above
# it.
MOVED_LIMIT = 1e-3


def _gated_delta(w, x, cut=None):
    """q, k, v = SiLU(W x), q and k L2-normalised a head; beta = 2 sig(W_b
    x); g = -exp(A_log) softplus(W_a x + dt_bias); S_t = e^g S + beta k (v
    - e^g S^T k)^T; o_t = S_t^T q / sqrt(DK).  ``cut``: S is zeroed there."""
    def silu(z):
        return z / (1.0 + np.exp(-z))
    q, k = (silu(x @ w[n]).reshape(T, H, DK) for n in "qk")
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = silu(x @ w["v"]).reshape(T, H, DV)
    beta = 2.0 / (1.0 + np.exp(-(x @ w["b"])))
    g = -np.exp(w["A_log"]) * np.logaddexp(0.0, x @ w["a"] + w["dt_bias"])
    s, out = np.zeros((H, DK, DV)), np.empty((T, H, DV))
    for t in range(T):
        if t == cut:
            s = np.zeros_like(s)
        s = np.exp(g[t])[:, None, None] * s
        err = v[t] - np.einsum("hkv,hk->hv", s, k[t])
        s = s + beta[t][:, None, None] * k[t][:, :, None] * err[:, None, :]
        out[t] = np.einsum("hkv,hk->hv", s, q[t]) / DK ** 0.5
    return out


def _moved(seed, draws):
    w = {k: np.asarray(v, np.float64)
         for k, v in weights.make_group(seed, 0, GDN, "float32",
                                        draws).items()}
    x = np.random.default_rng(seed).standard_normal((T, HID))
    full, cut = _gated_delta(w, x), _gated_delta(w, x, CUT)
    after = slice(CUT + 16, T)
    return (np.abs(full[after] - cut[after]).max()
            / np.abs(full[after]).max())


@pytest.mark.parametrize("seed", [1, SEED, 2 ** 32 + 5])
def test_a_dropped_carry_shows_under_the_published_draws_only(seed):
    published, today = _moved(seed, PUBLISHED), _moved(seed, None)
    assert published > MOVED_LIMIT > today, (published, today)
