"""Direct coverage for ``distributed/collectives.py`` (previously only
exercised indirectly through the training-parity subprocess) and the
``sharding.sanitize`` spec validator. The collectives run on REAL forced
host devices (tests/conftest.py sets the multi-device flag before jax
import), so the int8 wire format of the compressed psum crosses an actual
shard_map collective, not a simulation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed import collectives, sharding

pytestmark = pytest.mark.distributed


def _mesh(n, axis='data'):
    if jax.device_count() < n:
        pytest.skip(f'needs {n} devices, have {jax.device_count()}')
    return Mesh(np.asarray(jax.devices()[:n]), (axis,))


# ----------------------------------------------------------------------------
# psum_mean / plain collectives under real device shards
# ----------------------------------------------------------------------------
@pytest.mark.parametrize('n', [2, 4])
def test_psum_mean_matches_numpy(n):
    mesh = _mesh(n)
    f = jax.shard_map(lambda x: collectives.psum_mean(x, 'data'),
                      mesh=mesh, in_specs=P('data'), out_specs=P())
    x = jnp.arange(4.0 * n).reshape(n * 2, 2)
    got = np.asarray(jax.jit(f)(x))
    want = np.asarray(x).reshape(n, 2, 2).mean(axis=0)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_all_gather_tiled_reassembles_exactly():
    # the serving TP collective: a tiled all-gather on the last dim must
    # reassemble the original array bit-for-bit (head-major concat)
    mesh = _mesh(4)
    f = jax.shard_map(
        lambda x: jax.lax.all_gather(x, 'data', axis=x.ndim - 1,
                                     tiled=True),
        mesh=mesh, in_specs=P(None, 'data'), out_specs=P(None, None),
        check_vma=False)
    x = jnp.arange(32.0).reshape(2, 16)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)), np.asarray(x))


# ----------------------------------------------------------------------------
# compressed_psum: int8 error-feedback all-reduce
# ----------------------------------------------------------------------------
@pytest.mark.parametrize('n', [2, 4])
def test_compressed_psum_close_to_exact_mean(n):
    mesh = _mesh(n)
    f = jax.shard_map(
        lambda x, e: collectives.compressed_psum(x, 'data', e),
        mesh=mesh, in_specs=(P('data'), P('data')),
        out_specs=(P(), P('data')), check_vma=False)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n * 4, 3).astype(np.float32))
    ef = jnp.zeros_like(x)
    mean, new_ef = jax.jit(f)(x, ef)
    exact = np.asarray(x).reshape(n, 4, 3).mean(axis=0)
    # int8 quantization against the shared absmax scale: per-element error
    # of each shard's contribution is bounded by scale/2
    scale = np.abs(np.asarray(x)).max() / 127.0
    np.testing.assert_allclose(np.asarray(mean), exact, atol=scale)
    assert new_ef.shape == x.shape


def test_compressed_psum_error_feedback_compensates():
    """The point of error feedback: a bias too small for int8 at one step
    accumulates in ``ef`` and crosses the wire later — the RUNNING mean
    over many steps converges to the true value, instead of losing the
    bias to quantization forever."""
    mesh = _mesh(2)
    f = jax.jit(jax.shard_map(
        lambda x, e: collectives.compressed_psum(x, 'data', e),
        mesh=mesh, in_specs=(P('data'), P('data')),
        out_specs=(P(), P('data')), check_vma=False))
    # a large value sets the scale; the small bias is below one int8 step
    # (scale step = 100/127 ~ 0.787 >> 0.01)
    base = np.array([100.0, 0.01], np.float32)
    x = jnp.asarray(np.stack([base, base]))           # both shards equal
    ef = jnp.zeros_like(x)
    steps = 256
    acc = np.zeros_like(base)
    for _ in range(steps):
        mean, ef = f(x, ef)
        acc += np.asarray(mean)[0]        # local shards are (1, 2)
    got = acc / steps
    # WITHOUT feedback every step emits exactly 0 for the bias term (it
    # rounds below half a quantization step) -> running mean 0. WITH
    # feedback the residual accumulates and crosses the wire once it
    # reaches a step, so |mean - bias| <= scale_step / (2 * steps)
    bound = (100.0 / 127.0) / (2 * steps)
    assert abs(got[1] - 0.01) <= bound * 1.01, (got, bound)
    np.testing.assert_allclose(got[0], 100.0, rtol=1e-3)


def test_compressed_psum_int8_on_the_wire():
    """The wire contract: what crosses the psum is an int32 sum of int8
    payloads, not the f32 tensor — pinned by inspecting the jaxpr."""
    mesh = _mesh(2)
    f = jax.shard_map(
        lambda x, e: collectives.compressed_psum(x, 'data', e),
        mesh=mesh, in_specs=(P('data'), P('data')),
        out_specs=(P(), P('data')), check_vma=False)
    x = jnp.zeros((4, 3), jnp.float32)
    jx = str(jax.make_jaxpr(f)(x, x))
    assert 'psum' in jx
    assert 'i8[' in jx                      # int8 payload exists
    assert 'i32[' in jx                     # summed in int32


def test_tree_compressed_psum_structure():
    mesh = _mesh(2)
    tree = dict(a=jnp.ones((2, 2)), b=dict(c=jnp.full((2, 4), 2.0)))
    ef = jax.tree.map(jnp.zeros_like, tree)
    f = jax.shard_map(
        lambda t, e: collectives.tree_compressed_psum(t, 'data', e),
        mesh=mesh, in_specs=(P('data'), P('data')),
        out_specs=(P(), P('data')), check_vma=False)
    mean, new_ef = jax.jit(f)(tree, ef)
    assert set(mean) == {'a', 'b'} and set(new_ef) == {'a', 'b'}
    # identical shards: the mean is the value itself (up to quantization)
    np.testing.assert_allclose(np.asarray(mean['a']), 1.0, atol=1 / 127.0)
    np.testing.assert_allclose(np.asarray(mean['b']['c']), 2.0,
                               atol=2 / 127.0)


# ----------------------------------------------------------------------------
# sharding.sanitize: spec validation
# ----------------------------------------------------------------------------
def _mesh2d():
    if jax.device_count() < 4:
        pytest.skip('needs 4 devices')
    return Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ('data', 'model'))


def test_sanitize_none_mesh_passthrough():
    spec = P('data', ('data', 'model'))
    assert sharding.sanitize(spec, (3, 5), None) is spec


def test_sanitize_drops_nondividing_single_axis():
    mesh = _mesh2d()
    # 5 % 2 != 0: the single 'model' axis is silently dropped (qwen2-moe's
    # 60 experts over EP=16 etc. rely on this fall-back)
    assert sharding.sanitize(P('model', None), (5, 8), mesh) == P(None, None)
    assert sharding.sanitize(P('model', None), (6, 8), mesh) == \
        P('model', None)


def test_sanitize_rejects_stacked_overflow():
    mesh = _mesh2d()
    # stacked ('data','model') = 4-way on a dim of 2: an authoring bug —
    # must raise with the offending dim named, not silently drop
    with pytest.raises(ValueError, match=r'stacked mesh axes'):
        sharding.sanitize(P(('data', 'model'), None), (2, 8), mesh)
    with pytest.raises(ValueError, match=r'dim size 3 < 4'):
        sharding.sanitize(P(None, ('data', 'model')), (8, 3), mesh)
    # dividing stacked axes are fine...
    assert sharding.sanitize(P(('data', 'model'), None), (8, 3), mesh) == \
        P(('data', 'model'), None)
    # ...and 1-tuples keep the single-axis silent-drop semantics
    assert sharding.sanitize(P(('model',), None), (5, 8), mesh) == \
        P(None, None)


def test_sanitize_zero_dim_never_raises():
    # degenerate empty dims stay droppable, not an error
    mesh = _mesh2d()
    assert sharding.sanitize(P(('data', 'model'),), (0,), mesh) == \
        P(('data', 'model'),)
