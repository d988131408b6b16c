"""``reference/olmoe_f32.py`` against an independent numpy loop written from
the same published equations, token by token and expert by expert, at a tiny
size; and the rule that holds a program's routing to the reference's."""

import os

import numpy as np
import pytest

from benchmarks.harness import manifest

V, H, F, L, NH, D, E, K = 50, 16, 8, 2, 2, 8, 6, 2
CFG = dict(num_attention_heads=NH, num_key_value_heads=NH, hidden_size=H,
           rope_theta=10000.0, rms_norm_eps=1e-5, num_experts=E,
           num_experts_per_tok=K, norm_topk_prob=False, clip_qkv=None)


@pytest.fixture(scope="module")
def ref():
    return manifest.load_module(
        os.path.join(manifest.BENCH_DIR, "reference", "olmoe_f32.py"),
        "benchmarks_reference_olmoe_f32")


def weights(seed=0):
    rs = np.random.RandomState(seed)
    g = lambda *s: (rs.randn(*s) * 0.3).astype(np.float32)     # noqa: E731
    one = lambda n: (1 + 0.3 * rs.randn(n)).astype(np.float32)  # noqa: E731
    return {"embed": g(V, H), "final_norm": one(H), "head": g(H, V),
            "layers": [{"norm1": one(H), "norm2": one(H), "wq": g(H, NH * D),
                        "wk": g(H, NH * D), "wv": g(H, NH * D),
                        "q_norm": one(NH * D), "k_norm": one(NH * D),
                        "wo": g(NH * D, H), "router": g(H, E),
                        "w_gate": g(E, H, F), "w_up": g(E, H, F),
                        "w_down": g(E, F, H)} for _ in range(L)]}


def numpy_forward(w, ids, norm_topk):
    """float64, one token at a time, no vectorised routing."""
    f = lambda a: np.asarray(a, np.float64)                    # noqa: E731
    rms = lambda x, g: x / np.sqrt(np.mean(x * x) + 1e-5) * f(g)  # noqa: E731

    def rot(x, pos):
        out = np.empty_like(x)
        for h in range(NH):
            v = x[h * D:(h + 1) * D]
            for i in range(D // 2):
                a = pos / 10000.0 ** (2 * i / D)
                out[h * D + i] = v[i] * np.cos(a) - v[i + D // 2] * np.sin(a)
                out[h * D + i + D // 2] = (v[i + D // 2] * np.cos(a)
                                           + v[i] * np.sin(a))
        return out

    xs = [f(w["embed"])[t] for t in ids]
    choices = []
    for lw in w["layers"]:
        hs = [rms(x, lw["norm1"]) for x in xs]
        qs = [rot(rms(h @ f(lw["wq"]), lw["q_norm"]), p)
              for p, h in enumerate(hs)]
        ks = [rot(rms(h @ f(lw["wk"]), lw["k_norm"]), p)
              for p, h in enumerate(hs)]
        vs = [h @ f(lw["wv"]) for h in hs]
        layer_choice = []
        for p in range(len(xs)):
            att = np.zeros(NH * D)
            for h in range(NH):
                sl = slice(h * D, (h + 1) * D)
                s = np.array([qs[p][sl] @ ks[t][sl] / np.sqrt(D)
                              for t in range(p + 1)])
                pr = np.exp(s - s.max())
                pr /= pr.sum()
                att[sl] = sum(pr[t] * vs[t][sl] for t in range(p + 1))
            xs[p] = xs[p] + att @ f(lw["wo"])
        for p in range(len(xs)):
            h = rms(xs[p], lw["norm2"])
            lg = h @ f(lw["router"])
            pr = np.exp(lg - lg.max())
            pr /= pr.sum()
            top = sorted(range(E), key=lambda e: -pr[e])[:K]
            y = np.zeros(H)
            for e in top:
                gate = h @ f(lw["w_gate"])[e]
                act = gate / (1 + np.exp(-gate)) * (h @ f(lw["w_up"])[e])
                y += (pr[e] / (sum(pr[t] for t in top) if norm_topk else 1.0)
                      ) * (act @ f(lw["w_down"])[e])
            xs[p] = xs[p] + y
            layer_choice.append(top)
        choices.append(layer_choice)
    out = np.stack([rms(x, w["final_norm"]) @ f(w["head"]) for x in xs])
    return out, np.asarray(choices)


@pytest.mark.parametrize("norm_topk", [False, True])
def test_the_reference_is_the_published_equations(ref, norm_topk):
    w, ids = weights(), np.random.RandomState(1).randint(0, V, size=11)
    shape = ref.Shape.from_config({**CFG, "norm_topk_prob": norm_topk})
    got, routing = ref.forward(w, shape, ids, list(range(11)))
    want, choices = numpy_forward(w, ids, norm_topk)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)
    assert np.array_equal(np.sort(routing["choice"], -1), np.sort(choices, -1))
    assert routing["logits"].shape == (L, 11, E)
    assert (routing["margin"] > 0).all() and (routing["noise"] > 0).all()
    assert np.asarray(ref.logits_at(w, shape, ids, [3, 10])).shape == (2, V)


def test_the_loss_is_the_mean_cross_entropy_of_the_logits(ref):
    w, ids = weights(2), np.random.RandomState(3).randint(0, V, size=(2, 9))
    labels = np.concatenate([ids[:, 1:], np.full((2, 1), -1)], axis=1)
    shape = ref.Shape.from_config(CFG)
    want = []
    for row, lab in zip(ids, labels):
        lg = numpy_forward(w, row, False)[0]
        lse = np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)) \
            + lg.max(-1)
        want += [lse[i] - lg[i, lab[i]] for i in range(8)]
    assert ref.loss(w, shape, ids, labels) == pytest.approx(np.mean(want),
                                                            rel=1e-4)


def test_clip_qkv_is_refused_not_ignored(ref):
    with pytest.raises(ValueError):
        ref.Shape.from_config({**CFG, "clip_qkv": 8.0})


def test_the_allowance_grows_with_depth_as_a_random_walk(ref):
    lg = np.zeros((3, 1, E))
    lg[:, 0] = [3.0, 2.0, 1.0, 0.98, -1.0, -2.0]
    order = np.argsort(-lg, -1, kind="stable")
    routing = {"logits": lg, "choice": order[..., :K + 1],
               "noise": np.full((3, 1), 2e-3)}
    took_3_for_2 = np.array([[[0, 1, 3]]] * 3)
    v = ref.routing_agreement(routing, took_3_for_2, sigmas=4.0)
    # gap 0.02; allowance 4 x 2e-3 x sqrt(1 + 8 l) = 0.008, 0.024, 0.033
    assert (v["agree_share"], v["accepted"], v["refused"]) == (0.0, 2, 1)
    assert v["worst_refused_gap_over_allowance"] == pytest.approx(2.5)
