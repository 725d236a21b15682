"""Architecture families: the ``llama`` family keeps every bit the dense
benchmark had before the families were split out, a new architecture is
new files only, and a configuration that names no module fails by the
path it looked for.

``data/llama_bits.json`` was recorded with the benchmark's code as it
stood before the split (the dense model then lived in ``weights.py``,
``reference.py`` and ``work.py``), on the CPU with XLA's default
threading: a sha256 of every weight leaf for two seeds, one above 2**31;
of the float32 reference's logits and its fp8 control's, and of
``served_gaps``; and the step counts of three configurations.
"""
import hashlib
import textwrap

import jax
import numpy as np
import pytest

import config
import readers
from config import family, load_json
from conftest import DATA
from reference import served_gaps
from weights import base_key, freeze

BITS = load_json(DATA / "llama_bits.json")
TINY = load_json(DATA / "tiny.json")


def digest(x):
    a = np.asarray(x)
    return f"{a.dtype}{list(a.shape)}:" + hashlib.sha256(
        a.tobytes()).hexdigest()


def tokens(seed):
    seq = np.random.default_rng([seed, 5]).integers(0, 256, 50).astype(
        np.int32)
    t = np.zeros(BITS["length"], np.int32)
    t[:len(seq)] = seq
    return seq, t


@pytest.mark.parametrize("seed", BITS["seeds"])
def test_llama_weights_keep_their_bits(seed):
    p = family(TINY).program_params(TINY, seed)
    got = {jax.tree_util.keystr(k): digest(v)
           for k, v in jax.tree_util.tree_leaves_with_path(p)}
    assert got == BITS["weights"][str(seed)]


@pytest.mark.parametrize("mode", ["f32", "fp8"])
@pytest.mark.parametrize("seed", BITS["seeds"])
def test_llama_reference_keeps_its_bits(seed, mode):
    _, t = tokens(seed)
    with jax.default_matmul_precision("highest"):
        out = family(TINY).logits(TINY, base_key(seed), t, mode)
    assert digest(out) == BITS["logits"][f"{seed}.{mode}"]


@pytest.mark.parametrize("seed", BITS["seeds"])
def test_served_gaps_keep_their_bits(seed):
    seq, _ = tokens(seed)
    g, gc = served_gaps(TINY, seed, seq, 20, BITS["length"], True)
    assert [digest(g), digest(gc)] == BITS["gaps"][str(seed)]


@pytest.mark.parametrize("name", sorted(BITS["work"]))
def test_llama_work_counts_are_unchanged(name):
    c = TINY if name == "tiny" else config.load_config(name)
    w = family(c).work(c, c["engine"]["page_size"])
    for case in BITS["work"][name]:
        ctxs, owners = case["ctxs"], case["owners"]
        assert w.step_flops(ctxs, case["n_logits"]) == case["step_flops"]
        assert w.attn_flops(ctxs) == case["attn_flops"]
        assert w.attn_bytes(ctxs, owners) == case["attn_bytes"]
    assert list(readers.KERNEL_NAMES + w.kernel_names) == \
        BITS["kernel_names"]


def test_a_configuration_without_a_family_is_llama():
    assert "family" not in TINY
    assert family(TINY).__file__ == str(config.FAMILIES / "llama.py")


def test_freeze_keeps_nested_values_whole():
    c = {"b": 1.5, "a": "x", "rope_scaling": {"factor": 40, "type": "yarn"},
         "layer_types": ["dense", "moe", "moe"], "none": None}
    f = freeze(c)
    hash(f)
    assert [k for k, _ in f] == sorted(c)
    d = dict(f)
    assert (d["a"], d["b"], d["none"]) == ("x", 1.5, None)
    assert d["rope_scaling"] == (("factor", 40), ("type", "yarn"))
    assert d["layer_types"] == ("dense", "moe", "moe")
    # the scalar keys freeze as they always did
    scalars = {k: v for k, v in TINY.items()
               if isinstance(v, (int, float, str, bool))}
    assert freeze(scalars) == tuple(sorted(scalars.items()))


# An MLP-only block with a GeLU MLP: a whole family in one new file.
MLP_FAMILY = textwrap.dedent('''
    """A test family: RMSNorm and a GeLU MLP per layer, tied head."""
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp

    from reference import _ein, _rmsnorm
    from weights import base_key, embed_weights, freeze, layer_weights
    from work import Work


    def _leaves(c):
        d, f = c["hidden_size"], c["intermediate_size"]
        return [("ln", "scale", (d,), None),
                ("mlp", "w_in", (d, f), d ** -0.5),
                ("mlp", "w_out", (f, d), f ** -0.5)]


    def program_config(c):
        from repro.configs import get_config
        return dataclasses.replace(
            get_config(c["repo_config"]), n_layers=c["num_hidden_layers"],
            d_model=c["hidden_size"], d_ff=c["intermediate_size"],
            vocab_size=c["vocab_size"], act="gelu")


    @functools.partial(jax.jit, static_argnums=(0,))
    def _params(frozen, key):
        c = dict(frozen)
        served = jnp.dtype(c["torch_dtype"])
        layers = jax.vmap(lambda i: layer_weights(
            c, _leaves(c), key, i, served))(jnp.arange(c["num_hidden_layers"]))
        emb, final = embed_weights(c, key, served)
        return {"embed": emb, "final_norm": {"scale": final},
                "stages": {"stage_0": layers}}


    def program_params(c, seed):
        return _params(freeze(c), base_key(seed))


    @functools.partial(jax.jit, static_argnums=(0, 3))
    def _logits(frozen, key, tokens, mode):
        c = dict(frozen)
        emb, final = embed_weights(c, key, jnp.float32)

        def body(i, x):
            w = layer_weights(c, _leaves(c), key, i, jnp.float32)
            h = _rmsnorm(x, w["ln"]["scale"], c["rms_norm_eps"])
            up = jax.nn.gelu(_ein("sd,df->sf", h, w["mlp"]["w_in"], mode))
            return x + _ein("sf,fd->sd", up, w["mlp"]["w_out"], mode)

        x = jax.lax.fori_loop(0, c["num_hidden_layers"], body,
                              emb["table"][tokens])
        x = _rmsnorm(x, final, c["rms_norm_eps"])
        return _ein("sd,vd->sv", x, emb["table"], mode)


    def logits(c, key, tokens, mode):
        return _logits(freeze(c), key, tokens, mode)


    def work(c, page):
        d, f = c["hidden_size"], c["intermediate_size"]
        dense = 2 * c["num_hidden_layers"] * 2 * d * f
        return Work(
            lambda ctxs, n: dense * len(ctxs) + 2 * d * c["vocab_size"] * n,
            lambda ctxs: 0, lambda ctxs, owners: 0, ())
''')


def test_a_new_family_is_a_new_file(tmp_path, monkeypatch):
    llama = family(TINY)
    (tmp_path / "mlp_gelu.py").write_text(MLP_FAMILY)
    monkeypatch.setattr(config, "FAMILIES", tmp_path)
    c = {**TINY, "family": "mlp_gelu"}
    fam = family(c)
    assert fam is not llama
    cfg = fam.program_config(c)
    assert (cfg.act, cfg.n_layers, cfg.d_ff) == ("gelu", 2, 128)
    p = fam.program_params(c, 2**40 + 3)
    assert p["stages"]["stage_0"]["mlp"]["w_in"].shape == (2, 64, 128)
    # no token mixing: each position's logits depend on its token alone,
    # so the chain of the reference's first choices serves with gap 0
    vocab = c["vocab_size"]
    with jax.default_matmul_precision("highest"):
        first = np.asarray(fam.logits(c, base_key(2**40 + 3),
                                      np.arange(vocab, dtype=np.int32),
                                      "f32")).argmax(-1)
    seq = [5]
    for _ in range(30):
        seq.append(int(first[seq[-1]]))
    g, gc = served_gaps(c, 2**40 + 3, seq, 4, vocab, control=True)
    assert len(g) == 27 and float(g.max()) == 0.0 and (gc >= 0).all()
    seq[10] = (seq[10] + 1) % vocab
    g, _ = served_gaps(c, 2**40 + 3, seq, 4, vocab)
    assert g[10 - 4] > 0
    w = fam.work(c, 16)
    assert w.step_flops([0, 1, 2], 1) == 3 * 2 * 2 * 2 * 64 * 128 \
        + 2 * 64 * vocab


def test_an_unknown_family_names_the_file_it_looked_for():
    with pytest.raises(FileNotFoundError) as e:
        family({**TINY, "family": "no_such_family"})
    assert str(config.FAMILIES / "no_such_family.py") in str(e.value)


def test_a_family_without_its_four_functions_is_refused(tmp_path,
                                                        monkeypatch):
    (tmp_path / "half.py").write_text("def program_config(c):\n    pass\n")
    monkeypatch.setattr(config, "FAMILIES", tmp_path)
    with pytest.raises(AttributeError, match="program_params, logits, work"):
        family({"family": "half"})

