"""The program's names as a trace shows them: the ``jax.named_scope``
labels of its jitted bodies, and its Pallas kernels mapped to the logical
operation each computes.

On a TPU an operation's trace event is named by its HLO text; a Pallas
kernel is a ``custom-call`` to ``tpu_custom_call`` whose instruction is
named after the ``kernels/`` entry point that launched it
(``%grouped_matmul.46 = ... custom-call(...)``). The kernels have no
``name=`` yet; where an event carries the kernel body's function name
instead, that matches too."""

# A TPU trace gives its operations no scope, so each label goes with the
# module of the jitted function that opens it (``module=jit_<function>``):
# ``ServeEngine.prefill_step``'s ``step`` and ``pool_prefill_step``'s
# ``body`` open the prefill scopes, the scheduler's ``chunk_fn`` the
# decode one. (The fused launches, ``fused``, hold both and are left out;
# the benchmark's configurations do not turn them on.)
PREFILL_SCOPES = ("serve.diag_stage", "serve.pooled_diag_round",
                  "serve.fused_global_grid", "diag.antidiagonal",
                  "module=jit_step", "module=jit_body")
DECODE_SCOPES = ("serve.decode_chunk", "module=jit_chunk_fn")

# logical op -> (entry point, kernel body names)
KERNELS = {
    "grouped_matmul": ("grouped_matmul", ("_gmm_kernel", "_gmm_bias_kernel")),
    "grouped_matmul_armt_update": ("grouped_matmul_armt_update",
                                   ("_gmm_armt_kernel",)),
    "flash_attention": ("flash_attention", ("_flash_kernel",)),
    "armt_read": ("armt_read", ("_read_kernel",)),
    "armt_update": ("armt_update", ("_update_kernel",)),
    "decode_attention": ("decode_attention", ("_decode_kernel",)),
}


def kernel_ops(trace, op: str):
    """Trace operations of one logical kernel: a custom call whose
    instruction is named after the entry point, or an event named after
    the kernel body. Names are compared whole, so ``grouped_matmul`` does
    not match ``grouped_matmul_armt_update``, and the XLA operations
    around a kernel (pads, reshapes) are not counted as the kernel."""
    entry, bodies = KERNELS[op]
    out = []
    for o in trace.ops:
        if ((o.base == entry and "custom-call" in o.name)
                or o.base in bodies):
            out.append(o)
    return out
