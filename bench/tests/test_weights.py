import jax
import jax.numpy as jnp
import numpy as np

from config import load_json
from conftest import DATA
from weights import base_key, layer_weights, program_params


def test_stacked_layers_equal_each_layer_drawn_alone():
    """The program's stacked tree and the reference's one-layer draws,
    eager or inside a compiled loop, hold the same bits."""
    c = load_json(DATA / "tiny.json")
    p = program_params(c, 2**33 + 9)
    key = base_key(2**33 + 9)
    traced = jax.jit(lambda i: layer_weights(c, key, i, jnp.float32))
    for i in range(c["num_hidden_layers"]):
        for w in (layer_weights(c, key, i, jnp.float32), traced(i)):
            for group, leaves in w.items():
                for name, x in leaves.items():
                    got = p["stages"]["stage_0"][group][name][i]
                    assert np.array_equal(np.asarray(got), np.asarray(x))


def test_big_seeds_give_distinct_keys():
    a, b = base_key(5), base_key(5 + 2**31)
    assert not np.array_equal(jax.random.key_data(a),
                              jax.random.key_data(b))
    assert np.array_equal(jax.random.key_data(base_key(2**40 + 1)),
                          jax.random.key_data(base_key(2**40 + 1)))
