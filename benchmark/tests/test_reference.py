"""The plain reference held to itself, and the control shown to fail.

- the layer-by-layer step is the whole-model ``jax.grad`` and a plain
  AdamW, over three steps, with and without masters;
- the control (the reference with fp8 matmuls in the program's place)
  and the planted half-batch fault read over the tiny cell's limits,
  while the reference against itself reads 0.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rehearsal import TINY_LIMITS, tiny_arch
from benchmark import traffic
from benchmark.drivers import train_steps
from benchmark.reference import gpt as R

SEED = 2**31 + 5


def batches(arch, n=3, seed=SEED):
    gen = traffic.token_batches({"batch": 4, "seq": 32}, arch["vocab_size"],
                                seed)
    return [next(gen) for _ in range(n)]


def plain_steps(arch, job, seed, data):
    """jax.grad of the whole model and AdamW leaf by leaf."""
    cd = jnp.dtype(job["compute_dtype"])
    pd = jnp.float32 if job["master_weights"] else cd
    params = {n: v.astype(pd) for n, v in R.init_params(arch, seed, cd).items()}
    start = dict(params)
    m = {n: jnp.zeros(v.shape, jnp.float32) for n, v in params.items()}
    v = {n: jnp.zeros(p.shape, jnp.float32) for n, p in params.items()}
    opt = {k: float(job[k]) for k in ("learning_rate", "beta1", "beta2",
                                      "epsilon", "weight_decay")}
    losses, first = [], None
    for t, ids in enumerate(data, 1):
        w = {n: p.astype(cd).astype(jnp.float32) for n, p in params.items()}
        loss, g = jax.value_and_grad(R.loss_whole)(w, jnp.asarray(ids), arch)
        losses.append(float(loss))
        if first is None:
            first = g
        for n in params:
            p2, m[n], v[n] = R._adamw(params[n].astype(jnp.float32), g[n],
                                      m[n], v[n], jnp.float32(t), opt)
            params[n] = p2.astype(pd)
    return losses, first, {n: params[n].astype(jnp.float32)
                           - start[n].astype(jnp.float32) for n in params}


def per_layer_norm(x, name):
    """[parts], or [L, parts] for a block leaf, in plain numpy."""
    x = np.asarray(x, np.float64)
    parts = R.PARTS.get(name, 1)
    lead = x.shape[:1] if name in R.BLOCK_NAMES else ()
    x = x.reshape(lead + (-1, parts, x.shape[-1] // parts))
    return np.sqrt((x ** 2).sum(axis=(-3, -1)))


@pytest.mark.parametrize("master", [True, False])
def test_layer_by_layer_is_whole_model_grad(master):
    arch = tiny_arch()
    job = dict(arch["job"], master_weights=master)
    data = batches(arch)
    got = R.train_readings(arch, job, SEED, data)
    losses, grads, change = plain_steps(arch, job, SEED, data)
    np.testing.assert_allclose(got["losses"], losses, rtol=2e-6)
    for n in R.TOP_NAMES + R.BLOCK_NAMES:
        want = per_layer_norm(grads[n], n)
        # a part with no gradient (the key bias) is rounding on both
        # sides, and moves under Adam by that rounding alone
        real = want > 1e-6 * want.max()
        np.testing.assert_allclose(got["grad_norms"][n], want, rtol=1e-4,
                                   atol=1e-6 * want.max())
        np.testing.assert_allclose(got["change_norms"][n][real],
                                   per_layer_norm(change[n], n)[real],
                                   rtol=2e-3)


def over(numbers):
    return {n for n, v in numbers.items() if v > TINY_LIMITS[n]}


def test_reference_against_itself_reads_nought():
    arch = tiny_arch()
    data = batches(arch)
    ref = R.train_readings(arch, arch["job"], SEED, data)
    numbers = train_steps.compare(ref, ref)
    assert set(numbers.values()) == {0.0}


def test_control_and_fault_read_not_correct():
    arch = tiny_arch()
    data = batches(arch)
    ref = R.train_readings(arch, arch["job"], SEED, data)
    control = train_steps.compare(
        R.train_readings(arch, arch["job"], SEED, data, precision="fp8"), ref)
    assert over(control), control
    fault = train_steps.compare(
        R.train_readings(arch, arch["job"], SEED, data, half_batch=True), ref)
    assert "grad_norm_gap" in over(fault), fault
    # a state left unchanged reads 1 by the measure of the change
    still = dict(ref, change_norms={n: np.zeros_like(v) for n, v in
                                    ref["change_norms"].items()})
    assert train_steps.compare(still, ref)["change_norm_gap"] == 1.0


def test_the_key_bias_is_a_part_of_its_own():
    """The key third of the fused qkv bias has no gradient under softmax:
    it is its own entry, which the rule on the gradient then leaves out."""
    arch = tiny_arch()
    ref = R.train_readings(arch, arch["job"], SEED, batches(arch, 1))
    g = ref["grad_norms"]["qkv_b"]
    assert g.shape == (arch["num_layers"], 3)
    assert np.all(g[:, 1] < 1e-3 * np.minimum(g[:, 0], g[:, 2]))


def test_leaves_without_a_gradient_are_left_out_of_the_change():
    ref = {"losses": [1.0], "grad_norms": {"a": np.array([1.0, 1.0, 1e-9]),
                                           "b": np.array(2.0)},
           "change_norms": {"a": np.array([1.0, 1.0, 5.0]),
                            "b": np.array(1.0)}}
    got = {"losses": [1.0], "grad_norms": ref["grad_norms"],
           "change_norms": {"a": np.array([1.0, 1.0, 0.0]),
                            "b": np.array(1.0)}}
    assert train_steps.compare(got, ref)["change_norm_gap"] == 0.0


def test_seed_past_32_bits_gives_its_own_weights():
    arch = tiny_arch()
    a = R.init_params(arch, 5)["wte"]
    b = R.init_params(arch, 5 + 2**31)["wte"]
    c = R.init_params(arch, 5 + 2**31)["wte"]
    assert not np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    assert np.array_equal(np.asarray(b, np.float32),
                          np.asarray(c, np.float32))
