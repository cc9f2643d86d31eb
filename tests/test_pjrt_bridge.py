"""C++ PJRT bridge tests against the hermetic stub plugin.

The stub (native/pjrt_stub_plugin.cpp) is the CI stand-in for
libtpu.so behind the identical PJRT C ABI — the reference's
"same tests, different backend" pattern (SURVEY §4: nd4j-native
profile standing in for CUDA; CuDNNGradientChecks validating the fast
path against the baseline). These tests exercise the full bridge
surface: plugin load, client + device enumeration, MLIR compile,
H2D/D2H, execute, error paths, and buffer lifecycle.
"""
import numpy as np
import pytest

from deeplearning4j_tpu import pjrt

_STABLEHLO_ADD = """
module @jit_add {
  func.func public @main(%arg0: tensor<8xf32>, %arg1: tensor<8xf32>) -> tensor<8xf32> {
    %0 = stablehlo.add %arg0, %arg1 : tensor<8xf32>
    return %0 : tensor<8xf32>
  }
}
"""

_STABLEHLO_MUL = """
module @jit_mul {
  func.func public @main(%arg0: tensor<2x3xf32>, %arg1: tensor<2x3xf32>) -> tensor<2x3xf32> {
    %0 = stablehlo.multiply %arg0, %arg1 : tensor<2x3xf32>
    return %0 : tensor<2x3xf32>
  }
}
"""


@pytest.fixture(scope="module")
def runtime():
    if pjrt.get_bridge() is None:
        pytest.skip("native toolchain unavailable")
    stub = pjrt.stub_plugin_path()
    if stub is None:
        pytest.skip("stub plugin build failed")
    rt = pjrt.PjrtRuntime(plugin_path=stub)
    yield rt
    rt.close()


def test_plugin_load_and_client(runtime):
    major, minor = runtime.api_version
    assert major == 0 and minor > 0
    assert runtime.platform_name == "dl4j_stub"
    assert runtime.device_count == 1


def test_h2d_d2h_roundtrip(runtime):
    x = np.arange(24, dtype=np.float32).reshape(4, 6)
    buf = runtime.to_device(x)
    assert buf.nbytes == x.nbytes
    back = buf.to_numpy()
    assert back.dtype == np.float32 and back.shape == (4, 6)
    np.testing.assert_array_equal(back, x)
    buf.close()


def test_compile_and_execute_add(runtime):
    exe = runtime.compile(_STABLEHLO_ADD)
    assert exe.num_outputs == 1
    a = np.linspace(0, 1, 8).astype(np.float32)
    b = np.linspace(1, 2, 8).astype(np.float32)
    (out,) = exe(a, b)
    np.testing.assert_allclose(out, a + b, rtol=1e-6)
    exe.close()


def test_compile_and_execute_multiply_2d(runtime):
    exe = runtime.compile(_STABLEHLO_MUL)
    a = np.arange(6, dtype=np.float32).reshape(2, 3)
    b = np.full((2, 3), 3.0, np.float32)
    (out,) = exe(a, b)
    assert out.shape == (2, 3)
    np.testing.assert_allclose(out, a * b)
    exe.close()


def test_compile_error_surfaces_plugin_message(runtime):
    with pytest.raises(pjrt.PjrtError) as ei:
        runtime.compile("module @nope { }")
    assert "stablehlo" in str(ei.value)


def test_execute_wrong_arity_errors(runtime):
    exe = runtime.compile(_STABLEHLO_ADD)
    a = runtime.to_device(np.zeros(8, np.float32))
    with pytest.raises(pjrt.PjrtError):
        exe.execute([a])
    a.close()
    exe.close()


def test_missing_plugin_path_errors():
    if pjrt.get_bridge() is None:
        pytest.skip("native toolchain unavailable")
    with pytest.raises(pjrt.PjrtError) as ei:
        pjrt.PjrtRuntime(plugin_path="/nonexistent/libfoo.so")
    assert "plugin load failed" in str(ei.value)


def test_jax_lowering_feeds_the_bridge(runtime):
    """The intended production flow: jax traces/lowers a framework
    model step to StableHLO text; the native runtime compiles and runs
    it. The stub only knows single-op add, which jax emits for this
    function."""
    import jax
    import jax.numpy as jnp

    def f(x, y):
        return jnp.add(x, y)

    lowered = jax.jit(f).lower(jnp.zeros(8, jnp.float32),
                               jnp.zeros(8, jnp.float32))
    mlir_text = lowered.compiler_ir("stablehlo")
    exe = runtime.compile(str(mlir_text))
    a = np.ones(8, np.float32)
    (out,) = exe(a, a)
    np.testing.assert_allclose(out, 2 * np.ones(8, np.float32))
    exe.close()


def test_executable_cache_hit_and_miss(runtime):
    """Shape-keyed native executable cache (SURVEY §7: 'executable
    caching keyed on shapes')."""
    e1 = runtime.compile_cached(_STABLEHLO_ADD, key="add:8xf32")
    assert not e1.cache_hit
    assert runtime.exec_cache_size == 1
    e2 = runtime.compile_cached(_STABLEHLO_ADD, key="add:8xf32")
    assert e2.cache_hit
    assert runtime.exec_cache_size == 1
    e3 = runtime.compile_cached(_STABLEHLO_MUL, key="mul:2x3xf32")
    assert not e3.cache_hit
    assert runtime.exec_cache_size == 2
    a = np.arange(8, dtype=np.float32)
    (out,) = e2(a, a)
    np.testing.assert_allclose(out, a + a)
    # cached handles are cache-owned: close() must be a safe no-op
    e1.close()
    e4 = runtime.compile_cached(_STABLEHLO_ADD, key="add:8xf32")
    assert e4.cache_hit
    (out2,) = e4(a, a)
    np.testing.assert_allclose(out2, a + a)


def test_async_executor_fifo(runtime):
    """Native dispatch queue: submit N executions, wait out of order."""
    exe = runtime.compile(_STABLEHLO_ADD)
    with runtime.async_executor() as ex:
        bufs = []
        tickets = []
        for i in range(4):
            a = np.full(8, float(i), np.float32)
            b1, b2 = runtime.to_device(a), runtime.to_device(a)
            bufs += [b1, b2]
            tickets.append(ex.submit(exe, [b1, b2]))
        # wait in reverse order: results must match their own ticket
        for i in reversed(range(4)):
            (out,) = ex.wait(tickets[i])
            np.testing.assert_allclose(out.to_numpy(),
                                       np.full(8, 2.0 * i, np.float32))
            out.close()
        for b in bufs:
            b.close()
    exe.close()


def test_async_executor_error_path(runtime):
    """Wrong operand arity is rejected SYNCHRONOUSLY at submit (the r4
    guard — a mismatched execute was seen to drop a plugin's backend
    connection instead of erroring), and
    a failing NATIVE execution still surfaces its error at wait()
    without poisoning the queue (covered by disabling the Python-side
    guard, as happens for bytecode modules whose arity can't be
    parsed)."""
    exe = runtime.compile(_STABLEHLO_ADD)
    assert exe._expected_args == 2
    b = runtime.to_device(np.arange(8, dtype=np.float32))
    with runtime.async_executor() as ex:
        with pytest.raises(pjrt.PjrtError, match="takes 2 operands"):
            ex.submit(exe, [b])            # wrong arity: sync reject
        exe._expected_args = None          # unparsable-arity scenario
        bad = ex.submit(exe, [b])          # reaches the native path
        good_b2 = runtime.to_device(np.arange(8, dtype=np.float32))
        good = ex.submit(exe, [b, good_b2])
        with pytest.raises(pjrt.PjrtError):
            ex.wait(bad)
        (out,) = ex.wait(good)
        np.testing.assert_allclose(out.to_numpy(),
                                   2 * np.arange(8, dtype=np.float32))
        out.close()
        good_b2.close()
    b.close()
    exe.close()


def test_client_create_options_marshalling():
    """PJRT_NamedValue create_options through the C ABI (string, int64
    and bool kinds) — the path real plugins (libtpu) require for
    session/topology options; the stub accepts and ignores them, so
    this pins the marshalling itself."""
    stub = pjrt.stub_plugin_path()
    if stub is None:
        pytest.skip("stub plugin build unavailable")
    rt = pjrt.PjrtRuntime(plugin_path=stub, create_options={
        "topology": "v5e:1x1x1",     # kString
        "n_slices": 1,               # kInt64
        "remote_compile": False,     # kBool
        "session_id": "test-session",
    })
    try:
        assert rt.device_count >= 1
        x = np.arange(6, dtype=np.float32).reshape(2, 3)
        np.testing.assert_array_equal(rt.to_device(x).to_numpy(), x)
    finally:
        rt.close()


def test_h2d_d2h_rank3_and_rank4_roundtrip(runtime):
    """Rank>=3 layout regression (round-3: the real plugin's default
    layout for rank>=3 is a permuted order — the bridge now pins
    C-order on both directions; on the real chip this corrupted every
    conv weight before the fix)."""
    for shape in [(2, 3, 4), (2, 3, 4, 5), (5, 5, 1, 20)]:
        x = (np.arange(np.prod(shape), dtype=np.float32)
             .reshape(shape) + 1.5)
        buf = runtime.to_device(x)
        np.testing.assert_array_equal(buf.to_numpy(), x)
        buf.close()
