"""The search path's Pallas kernels compile for a TPU v5e.

Interpret mode (tests/test_kernels.py) cannot see what Mosaic refuses: a
slice not aligned to the tiling, more VMEM than a kernel may use.  These
tests lower each kernel through its ``kernels/ops.py`` wrapper, padding
included, at the shapes the search path feeds it, and compile it for a
v5e that is described, not attached.  The compiled program must hold a
Mosaic kernel (``tpu_custom_call``): the kernel was not lowered in
interpret mode.  Nothing runs; this says nothing about values or times.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import ga as ga_lib
from repro.core import policy as policy_lib
from repro.costmodel import workloads
from repro.costmodel.layers import NUM_FIELDS
from repro.kernels import ops
from repro.kernels.costmodel_eval import TN

N_MOBILENET = len(workloads.mobilenet_v2())
GA_POP = ga_lib.GAConfig().population
# One mobilenet_v2 GA generation through the batcher: population x layers
# points, padded to whole (TN,) lane rows -- eval_point_rows' packing.
BATCHER_ROWS = -(-GA_POP * N_MOBILENET // TN)
PCFG = policy_lib.PolicyConfig()
LSTM_SHAPES = [(1, PCFG.obs_dim), (1, PCFG.hidden), (1, PCFG.hidden),
               (PCFG.obs_dim, 4 * PCFG.hidden),
               (PCFG.hidden, 4 * PCFG.hidden), (4 * PCFG.hidden,)]


def lstm_step_grad(*args):
    """The policy step as REINFORCE differentiates it: a Mosaic kernel has
    no autodiff rule of its own, so this needs ops' custom VJP."""
    def loss(*a):
        return jnp.sum(ops.lstm_step(*a)[0] ** 2)
    return jax.grad(loss, argnums=tuple(range(len(args))))(*args)


# kernel -> (ops wrapper, argument shapes as the search path passes them)
CASES = {
    "cost_eval_padded": (
        ops.batched_cost,
        [(N_MOBILENET, NUM_FIELDS)] + [(GA_POP, N_MOBILENET)] * 3),
    "cost_eval_multi_padded": (
        ops.batched_cost_multi,
        [(BATCHER_ROWS, TN, NUM_FIELDS)] + [(BATCHER_ROWS, TN)] * 3),
    "lstm_cell_padded": (ops.lstm_step, LSTM_SHAPES),
    "lstm_cell_padded_grad": (lstm_step_grad, LSTM_SHAPES),
}


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile cache
    off: its entries for a described chip cannot be read back here."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 -- no libtpu / no TPU plugin
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(kernel, one_chip, monkeypatch):
    wrapper, shapes = CASES[kernel]
    # ops picks the kernel and interpret mode from the default backend,
    # which is the CPU here; the target of this compile is the described
    # TPU.
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(wrapper).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
