"""TPU kernels: fused ops the module zoo and trainer dispatch into.

- ``layernorm`` — Pallas fused LayerNorm (one VMEM residency per row block);
- ``flash_attention`` — streaming-softmax attention (imported on demand);
- ``grouped_matmul`` — the experts of a routed layer in one call (imported on demand);
- ``token_sum`` — a routed layer's rows summed back to their tokens (imported on demand);
- ``conv_bn`` — fused conv→bn(→relu) with inference-time BN folding;
- ``fused_update`` — flat-param (dtype-grouped vector) optimizer updates.
"""

from bigdl_tpu.kernels.layernorm import fused_layer_norm

__all__ = ["fused_layer_norm", "FusedConvBNReLU", "fold_bn_into_conv",
           "fold_bn_scale_shift", "FlatParamUpdate", "flat_supported"]


def __getattr__(name):
    # conv_bn/fused_update pull in the nn/optim packages — import lazily so
    # `from bigdl_tpu.kernels import fused_layer_norm` (the normalization
    # layer's hot path) never pays for or cycles through them
    if name in ("FusedConvBNReLU", "fold_bn_into_conv", "fold_bn_scale_shift",
                "fold_enabled"):
        from bigdl_tpu.kernels import conv_bn
        return getattr(conv_bn, name)
    if name in ("FlatParamUpdate", "flat_supported", "FlatSpec"):
        from bigdl_tpu.kernels import fused_update
        return getattr(fused_update, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
