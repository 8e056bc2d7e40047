"""Model families shipped with the framework.

The reference ships only test MLPs and an MNIST example
(reference tests/utils.py:96-145, examples/ray_ddp_example.py); the
BASELINE.json configs additionally require ResNet/CIFAR, BERT fine-tune,
and Llama-3-8B FSDP — all provided here as TpuModules.
"""
from ray_lightning_tpu.models.bert import (
    BertClassifierModule,
    BertConfig,
    BertEncoder,
    BertForSequenceClassification,
)
from ray_lightning_tpu.models.hf_interop import (
    bert_classifier_params_from_hf,
    bert_params_from_hf,
    llama_params_from_hf,
)
from ray_lightning_tpu.models.llama import (
    Llama,
    LlamaConfig,
    LlamaModule,
    generate,
    init_cache,
)
from ray_lightning_tpu.models.mla_moe import MlaMoe, MlaMoeConfig
from ray_lightning_tpu.models.mlp import MLP, MLPClassifier, MNISTClassifier
from ray_lightning_tpu.models.moe import (
    MoEClassifierModule,
    MoEMLP,
    moe_param_specs,
)
from ray_lightning_tpu.models.ssm_hybrid import SsmHybrid, SsmHybridConfig
from ray_lightning_tpu.models.delta_hybrid import (
    DeltaHybrid, DeltaHybridConfig,
)
from ray_lightning_tpu.models.swa_moe import (
    SwaMoe, SwaMoeConfig, SwaMoeModule,
)
from ray_lightning_tpu.models.window_moe import WindowMoe, WindowMoeConfig
from ray_lightning_tpu.models.conv_moe import ConvMoe, ConvMoeConfig
from ray_lightning_tpu.models.resnet import (
    ResNet,
    ResNetModule,
    resnet18,
    resnet34,
    resnet50,
)

__all__ = [
    "BertClassifierModule",
    "BertConfig",
    "BertEncoder",
    "BertForSequenceClassification",
    "Llama",
    "LlamaConfig",
    "LlamaModule",
    "bert_classifier_params_from_hf",
    "bert_params_from_hf",
    "generate",
    "init_cache",
    "llama_params_from_hf",
    "MlaMoe",
    "MlaMoeConfig",
    "MLP",
    "MLPClassifier",
    "MNISTClassifier",
    "MoEClassifierModule",
    "MoEMLP",
    "moe_param_specs",
    "ResNet",
    "ResNetModule",
    "resnet18",
    "resnet34",
    "resnet50",
    "ConvMoe",
    "ConvMoeConfig",
    "DeltaHybrid",
    "DeltaHybridConfig",
    "SsmHybrid",
    "SsmHybridConfig",
    "SwaMoe",
    "SwaMoeConfig",
    "SwaMoeModule",
    "WindowMoe",
    "WindowMoeConfig",
]
