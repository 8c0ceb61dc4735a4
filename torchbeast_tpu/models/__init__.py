"""Model registry.

`create_model("shallow"|"deep", ...)` mirrors the reference's two families:
MonoBeast's AtariNet (monobeast.py:545) and PolyBeast's deep ResNet
(polybeast_learner.py:134).
"""

from torchbeast_tpu.models.atari_net import AtariNet  # noqa: F401
from torchbeast_tpu.models.cores import LSTMCore  # noqa: F401
from torchbeast_tpu.models.mlp import MLPNet  # noqa: F401
from torchbeast_tpu.models import olmoe
from torchbeast_tpu.models.olmoe import OLMoENet  # noqa: F401
from torchbeast_tpu.models.pipelined import PipelinedMLPNet  # noqa: F401
from torchbeast_tpu.models.resnet import ResNet  # noqa: F401
from torchbeast_tpu.models.transformer import TransformerNet  # noqa: F401
from torchbeast_tpu.models.transformer_pp import (  # noqa: F401
    PipelinedTransformerNet,
)

_REGISTRY = {
    "shallow": AtariNet,
    "atari": AtariNet,
    "deep": ResNet,
    "resnet": ResNet,
    "mlp": MLPNet,
    "pipelined_mlp": PipelinedMLPNet,
    "transformer": TransformerNet,
    "pipelined_transformer": PipelinedTransformerNet,
    "olmoe": OLMoENet,
}
# Families whose memory is a KV cache: --use_lstm does not apply.
_KV_CACHE_FAMILIES = (TransformerNet, PipelinedTransformerNet, OLMoENet)


def create_model(name: str, num_actions: int, use_lstm: bool = False, **kwargs):
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"Unknown model {name!r}; available: {sorted(_REGISTRY)}"
        ) from None
    if cls in _KV_CACHE_FAMILIES and use_lstm:
        raise ValueError(
            "--use_lstm does not apply to the transformer family (its "
            "memory is the KV cache); drop the flag"
        )
    if cls is OLMoENet:
        # The published widths, read now (a test shrinks the table).
        kwargs = {**olmoe.PUBLISHED, **kwargs}
    return cls(num_actions=num_actions, use_lstm=use_lstm, **kwargs)
