"""Single-process eager API semantics (size == 1)."""

import numpy as np
import pytest

import horovod_tpu as hvd


@pytest.fixture(autouse=True)
def fresh_runtime():
    # ensure a clean single-process runtime per test
    hvd.shutdown()
    hvd.init()
    yield
    hvd.shutdown()


def test_basics():
    assert hvd.rank() == 0
    assert hvd.size() == 1
    assert hvd.local_rank() == 0
    assert hvd.local_size() == 1
    assert hvd.cross_rank() == 0
    assert hvd.cross_size() == 1
    assert hvd.is_homogeneous()
    assert hvd.xla_built()
    assert not hvd.nccl_built()
    assert not hvd.mpi_built()


def test_allreduce_identity():
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    np.testing.assert_allclose(hvd.allreduce(x, op=hvd.Sum), x)
    np.testing.assert_allclose(hvd.allreduce(x, op=hvd.Average), x)


def test_allreduce_scaling():
    x = np.ones(4, np.float32)
    out = hvd.allreduce(x, op=hvd.Sum, prescale_factor=2.0,
                        postscale_factor=3.0)
    np.testing.assert_allclose(out, np.full(4, 6.0))


def test_async_poll_synchronize():
    h = hvd.allreduce_async(np.ones(3, np.float32), op=hvd.Sum)
    assert hvd.poll(h)
    np.testing.assert_allclose(hvd.synchronize(h), np.ones(3))


def test_allgather_broadcast_alltoall():
    x = np.arange(4, dtype=np.int64)
    np.testing.assert_array_equal(hvd.allgather(x), x)
    np.testing.assert_array_equal(hvd.broadcast(x, root_rank=0), x)
    np.testing.assert_array_equal(hvd.alltoall(x), x)
    with pytest.raises(ValueError):
        hvd.broadcast(x, root_rank=3)


def test_reducescatter_single():
    # size 1: the reduction of one rank's tensor, scattered to the one
    # rank — identity.  Scalars and unsupported ops are named errors.
    x = np.arange(6, dtype=np.float32).reshape(3, 2)
    np.testing.assert_array_equal(hvd.reducescatter(x, op=hvd.Sum), x)
    with pytest.raises(ValueError, match="at least one dimension"):
        hvd.reducescatter(np.float32(1.0), op=hvd.Sum)
    with pytest.raises(ValueError, match="does not support"):
        hvd.reducescatter(x, op=hvd.Adasum)


def test_join_and_barrier():
    assert hvd.join() == 0
    hvd.barrier()


def test_jax_array_roundtrip():
    import jax.numpy as jnp

    x = jnp.arange(5, dtype=jnp.float32)
    out = hvd.allreduce(x, op=hvd.Sum)
    assert "Array" in type(out).__name__
    np.testing.assert_allclose(np.asarray(out), np.arange(5))


def test_torch_tensor_roundtrip():
    torch = pytest.importorskip("torch")
    x = torch.arange(5, dtype=torch.float32)
    out = hvd.allreduce(x, op=hvd.Sum)
    assert isinstance(out, torch.Tensor)
    np.testing.assert_allclose(out.numpy(), np.arange(5))


def test_broadcast_object_and_parameters():
    obj = hvd.broadcast_object({"a": 1, "b": [2, 3]})
    assert obj == {"a": 1, "b": [2, 3]}
    params = {"w": np.ones((2, 2), np.float32), "b": np.zeros(2, np.float32)}
    out = hvd.broadcast_parameters(params)
    np.testing.assert_allclose(out["w"], params["w"])


def test_compression_fp16_eager():
    x = np.linspace(-2, 2, 16).astype(np.float32)
    out = hvd.allreduce(x, op=hvd.Sum, compression=hvd.Compression.fp16)
    assert out.dtype == np.float32
    np.testing.assert_allclose(out, x, rtol=1e-2)


def test_bridge_misuse_inside_shard_map_raises():
    """A bridge collective traced inside shard_map must raise TypeError at
    trace time (the un-guarded failure mode is a hang: one enqueue per
    shard under a single tensor name)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.ops import bridge

    devs = np.array(jax.devices()[:2])
    if devs.size < 2:
        pytest.skip("needs >=2 virtual devices")
    mesh = Mesh(devs, ("dp",))

    def body(x):
        return bridge.allreduce(x, name="misuse")

    f = jax.shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    with pytest.raises(TypeError, match="shard_map"):
        f(jnp.ones((4,), jnp.float32))


def test_bridge_misuse_inside_pmap_raises():
    """Same misuse guard for pmap: a raise rather than a hang."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import bridge

    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 virtual devices")

    def body(x):
        return bridge.allreduce(x, name="misuse.pmap")

    f = jax.pmap(body)
    x = jnp.ones((2, 4), jnp.float32)
    with pytest.raises(TypeError, match="pmap"):
        f(x)
