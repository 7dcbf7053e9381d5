"""Model zoo: Flax models for the baseline configs (BASELINE.md).

- `mnist_cnn`: MNIST Keras-CNN analogue (README example / random-search HPO)
- `resnet`: ResNet for CIFAR-10 (ASHA sweep config)
- `bert`: BERT-base-style encoder (GLUE fine-tune HPO config)
- `llama`: Llama-style decoder + LoRA (the LoRA-sweep config; flagship)
- `sdar`: SDAR-MoE block-diffusion decoder holding a share of its experts
- `nemotron_h`: Nemotron-H decoder from a pattern of state-space, expert and
  attention blocks, holding a share of its routed experts
- `ouro`: Ouro looped decoder: one stack of sandwich-norm layers applied
  several times over shared weights, an exit with a gate after every pass
- `surgery`: ablatable-module helpers for LOCO model surgery
"""

from maggy_tpu.models.mnist_cnn import MnistCNN, MnistMLP
from maggy_tpu.models.resnet import ResNet
from maggy_tpu.models.bert import BertEncoder, BertConfig
from maggy_tpu.models.llama import Llama, LlamaConfig
from maggy_tpu.models.moe import ExpertShareMLP, MoEMLP
from maggy_tpu.models.nemotron_h import NemotronH, NemotronHConfig
from maggy_tpu.models.ouro import Ouro, OuroConfig
from maggy_tpu.models.sdar import SdarMoe, SdarMoeConfig
from maggy_tpu.models.vit import ViT, ViTConfig

__all__ = ["MnistCNN", "MnistMLP", "ResNet", "BertEncoder", "BertConfig",
           "Llama", "LlamaConfig", "MoEMLP", "ExpertShareMLP", "NemotronH",
           "NemotronHConfig", "Ouro", "OuroConfig", "SdarMoe",
           "SdarMoeConfig", "ViT", "ViTConfig"]
