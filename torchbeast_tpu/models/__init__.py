"""Model registry.

`create_model("shallow"|"deep", ...)` mirrors the reference's two families:
MonoBeast's AtariNet (monobeast.py:545) and PolyBeast's deep ResNet
(polybeast_learner.py:134).
"""

import dataclasses

from torchbeast_tpu.models.atari_net import AtariNet  # noqa: F401
from torchbeast_tpu.models.cores import LSTMCore  # noqa: F401
from torchbeast_tpu.models.mlp import MLPNet  # noqa: F401
from torchbeast_tpu.models import (
    granite4,
    kanana2,
    lfm2,
    ling3,
    mellum2,
    nemotron3,
    olmoe,
    ouro,
    phi4flash,
    qwen3next,
    trinity,
    xing4,
)
from torchbeast_tpu.models.granite4 import Granite4Net  # noqa: F401
from torchbeast_tpu.models.kanana2 import Kanana2Net  # noqa: F401
from torchbeast_tpu.models.lfm2 import Lfm2Net  # noqa: F401
from torchbeast_tpu.models.ling3 import Ling3Net  # noqa: F401
from torchbeast_tpu.models.mellum2 import Mellum2Net  # noqa: F401
from torchbeast_tpu.models.nemotron3 import Nemotron3Net  # noqa: F401
from torchbeast_tpu.models.olmoe import OLMoENet  # noqa: F401
from torchbeast_tpu.models.ouro import OuroNet  # noqa: F401
from torchbeast_tpu.models.phi4flash import Phi4FlashNet  # noqa: F401
from torchbeast_tpu.models.pipelined import PipelinedMLPNet  # noqa: F401
from torchbeast_tpu.models.qwen3next import Qwen3NextNet  # noqa: F401
from torchbeast_tpu.models.resnet import ResNet  # noqa: F401
from torchbeast_tpu.models.transformer import TransformerNet  # noqa: F401
from torchbeast_tpu.models.transformer_pp import (  # noqa: F401
    PipelinedTransformerNet,
)
from torchbeast_tpu.models.trinity import TrinityNet  # noqa: F401
from torchbeast_tpu.models.xing4 import Xing4Net  # noqa: F401

# The one list of policy families: `--model`'s choices in every driver,
# and what `create_model` builds.
_REGISTRY = {
    "shallow": AtariNet,
    "deep": ResNet,
    "mlp": MLPNet,
    "pipelined_mlp": PipelinedMLPNet,
    "transformer": TransformerNet,
    "pipelined_transformer": PipelinedTransformerNet,
    "olmoe": OLMoENet,
    "mellum2": Mellum2Net,
    "ouro": OuroNet,
    "kanana2": Kanana2Net,
    "nemotron3": Nemotron3Net,
    "qwen3next": Qwen3NextNet,
    "lfm2": Lfm2Net,
    "phi4flash": Phi4FlashNet,
    "xing4": Xing4Net,
    "trinity": TrinityNet,
    "granite4": Granite4Net,
    "ling3": Ling3Net,
}
# A family whose widths are a published table (its module's `PUBLISHED`,
# keyed by the class's fields): read when the model is built, so that a
# test shrinks the family there.
_PUBLISHED_TABLES = {
    OLMoENet: olmoe, Mellum2Net: mellum2, OuroNet: ouro, Kanana2Net: kanana2,
    Nemotron3Net: nemotron3, Qwen3NextNet: qwen3next, Lfm2Net: lfm2,
    Phi4FlashNet: phi4flash, Xing4Net: xing4, TrinityNet: trinity,
    Granite4Net: granite4, Ling3Net: ling3,
}
MODEL_NAMES = tuple(_REGISTRY)


def takes_flag(name: str, field: str) -> bool:
    """Whether `--<field>` sets a field of family `name`'s module: its
    class declares the field and does not refuse it (a class lists in
    `flag_refused_fields` the fields another flag or its published
    table sets)."""
    cls = _REGISTRY[name]
    return field not in getattr(cls, "flag_refused_fields", ()) and any(
        f.name == field for f in dataclasses.fields(cls)
    )


def remat_lever(name: str):
    """What family `name`'s class says `--remat` reaches in it beside
    the LSTM scan (runtime/remat_plan.py): "blocks" where the `remat`
    field rematerialises each block, None where nothing is said (the
    deep ResNet's three stages are the planner's own)."""
    return getattr(_REGISTRY.get(name), "remat_lever", None)


def families_taking(field: str):
    """The registry's names of the families `--<field>` reaches."""
    return [name for name in _REGISTRY if takes_flag(name, field)]


def create_model(name: str, num_actions: int, use_lstm: bool = False, **kwargs):
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"Unknown model {name!r}; available: {sorted(_REGISTRY)}"
        ) from None
    if getattr(cls, "memory_is_kv_cache", False) and use_lstm:
        raise ValueError(
            "--use_lstm does not apply to the transformer family (its "
            "memory is the KV cache); drop the flag"
        )
    if cls in _PUBLISHED_TABLES:
        kwargs = {**_PUBLISHED_TABLES[cls].PUBLISHED, **kwargs}
    return cls(num_actions=num_actions, use_lstm=use_lstm, **kwargs)
