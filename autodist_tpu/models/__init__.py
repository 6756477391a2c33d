"""Model zoo — the reference's benchmark families, TPU-first.

ResNet-50/101, VGG16, DenseNet121, InceptionV3 (imagenet.py parity),
BERT-base/large (bert.py parity), lm1b LSTM (examples/lm1b parity),
NCF (MovieLens parity), plus the flagship TransformerLM (new scope for
long-context/multi-dim parallelism).
"""
from autodist_tpu.models.base import ModelSpec, cross_entropy_loss  # noqa: F401
from autodist_tpu.models.bert import bert, bert_base, bert_large  # noqa: F401
from autodist_tpu.models.generate import make_generator  # noqa: F401
from autodist_tpu.models.quantize import (  # noqa: F401
    dequantize_lm_params,
    quantize_lm_params,
)
from autodist_tpu.models.speculative import (  # noqa: F401
    make_speculative_generator,
)
from autodist_tpu.models.densenet import densenet121  # noqa: F401
from autodist_tpu.models.inception import inception_v3  # noqa: F401
from autodist_tpu.models.lm1b import lm1b  # noqa: F401
from autodist_tpu.models.lora import (  # noqa: F401
    lora_init,
    lora_merge,
    lora_setup,
)
from autodist_tpu.models.mla_moe_lm import mla_moe_lm  # noqa: F401
from autodist_tpu.models.moe_lm import moe_transformer_lm  # noqa: F401
from autodist_tpu.models.ncf import ncf  # noqa: F401
from autodist_tpu.models.pipelined_lm import pipelined_transformer_lm  # noqa: F401
from autodist_tpu.models.pipelined_moe_lm import (  # noqa: F401
    pipelined_moe_transformer_lm,
)
from autodist_tpu.models.resnet import resnet50, resnet101  # noqa: F401
from autodist_tpu.models.transformer_lm import transformer_lm  # noqa: F401
from autodist_tpu.models.vgg import vgg16  # noqa: F401

ALL_MODELS = {
    "resnet50": resnet50,
    "resnet101": resnet101,
    "vgg16": vgg16,
    "densenet121": densenet121,
    "inception_v3": inception_v3,
    "bert": bert,
    "lm1b": lm1b,
    "ncf": ncf,
    "transformer_lm": transformer_lm,
    # pipelined_transformer_lm / moe_transformer_lm are mesh-parameterized;
    # construct them directly.  mla_moe_lm's defaults are one chip's share
    # of a 30B model (576 M parameters): construct it with your sizes.
}
