"""The comparison that decides ``correct``, and the float8 rounding of
its control."""

import numpy as np

from bench import check


def test_gaps_are_relative_and_fail_on_non_finite():
    want = [{"crps": np.array([1.0, 2.0, 0.0]), "ens_rmse": np.ones(3),
             "ssr": np.ones(3)}]
    got = [{"crps": np.array([1.01, 2.0, 0.0]), "ens_rmse": np.ones(3),
            "ssr": np.ones(3)}]
    assert abs(check.gaps(got, want)["scores_gap"] - 0.01) < 1e-12
    got[0]["ssr"] = np.array([1.0, np.nan, 1.0])
    numbers = check.gaps(got, want)
    assert numbers["scores_gap"] == float("inf")
    assert not check.judge(numbers, {"scores_gap": 1.0})


def test_spectrum_gap_is_a_relative_l2_per_channel():
    w = np.array([[3.0, 4.0], [1.0, 0.0]])
    g = np.array([[3.0, 4.5], [1.0, 0.0]])
    assert abs(check.gaps([{"spectrum": g}], [{"spectrum": w}])
               ["spectrum_gap"] - 0.1) < 1e-12


def test_judge_needs_every_limited_number():
    assert check.judge({"a": 1.0}, {"a": 1.0})
    assert not check.judge({}, {"a": 1.0})
    assert not check.judge({"a": 1.0}, {"a": None})


def test_fp8_rounding_keeps_three_mantissa_bits():
    import jax.numpy as jnp

    from bench import fp8
    x = jnp.asarray([448.0, 1.0, 1.0625, -3.3, 0.0])
    got = np.asarray(fp8.round_f8(x))
    assert got[0] == 448.0 and got[1] == 1.0 and got[4] == 0.0
    assert got[2] == 1.0                   # 1 + 2**-4 rounds to even
    assert abs(got[3] + 3.25) < 1e-6       # steps of 0.25 in [2, 4)
    y = jnp.asarray([1e-3, 2e-3])          # scaled: the largest is 448
    assert np.allclose(np.asarray(fp8.round_f8(y)), [1e-3, 2e-3])
    assert fp8.round_f8(jnp.arange(3)).dtype == jnp.arange(3).dtype


def test_fp8_quantizes_every_matmul_and_only_matmuls():
    import jax
    import jax.numpy as jnp

    from bench import fp8
    a = jnp.sin(jnp.arange(16.0)).reshape(4, 4)

    def f(x):
        return jax.lax.scan(lambda c, r: (c + jnp.tanh(r @ x), None),
                            jnp.zeros(4), x)[0] + jnp.sin(x).sum()

    want = f(a)
    got = fp8.quantized(f)(a)
    rel = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert 1e-4 < rel < 0.1
    assert np.allclose(fp8.quantized(lambda x: jnp.sin(x) * 2)(a),
                       jnp.sin(a) * 2)
