"""Model zoo backing the reference's examples and benchmarks.

The reference itself ships no model library — its examples and README
benchmarks use MNIST convnets, word2vec, and tf_cnn_benchmarks'
ResNet-101 / Inception V3 / VGG-16 (`README.md:27-32`, SURVEY §6). These
TPU-first implementations (flax.linen, NHWC, bfloat16-friendly) back
`examples/`, `chip_smoke.py` and the scaling-efficiency targets in
BASELINE.md.
"""

from horovod_tpu.models.mnist import MnistConvNet
from horovod_tpu.models.resnet import ResNet, ResNet50, ResNet101, ResNet152
from horovod_tpu.models.vgg import VGG16
from horovod_tpu.models.inception import InceptionV3
from horovod_tpu.models.word2vec import Word2Vec
from horovod_tpu.models.lora import (graft_base, lora_label_fn,
                                     lora_mask, merge_lora)
from horovod_tpu.models.speculative import generate_speculative
from horovod_tpu.models.bert import (BertBase, BertLarge, BertMLM,
                                     chunked_mlm_loss,
                                     make_mlm_batch, make_mlm_train_step,
                                     mlm_loss)
from horovod_tpu.models.vit import VisionTransformer, ViT_B16, ViT_S16
from horovod_tpu.models.train import make_cnn_train_step
from horovod_tpu.models.transformer import (
    TransformerLM, generate, generate_bucketed, init_lm_state,
    lm_fsdp_specs, make_lm_eval_step, make_lm_train_step,
    serving_params,
)

__all__ = [
    "MnistConvNet", "ResNet", "ResNet50", "ResNet101", "ResNet152",
    "VGG16", "InceptionV3", "Word2Vec", "VisionTransformer",
    "ViT_B16", "ViT_S16", "make_cnn_train_step",
    "BertBase", "BertLarge", "BertMLM", "chunked_mlm_loss",
    "make_mlm_batch",
    "make_mlm_train_step", "mlm_loss",
    "graft_base", "lora_label_fn", "lora_mask", "merge_lora",
    "generate_speculative",
    "TransformerLM", "generate", "generate_bucketed", "init_lm_state",
    "lm_fsdp_specs", "make_lm_eval_step", "make_lm_train_step",
    "serving_params",
]
