from tpusystem.models.mlp import MLP
from tpusystem.models.gpt2 import GPT2, GPT2Pipelined, gpt2_small, gpt2_tiny
from tpusystem.models.llama import Llama, llama3_8b, llama_tiny
from tpusystem.models.deepseek import DeepSeekV2, deepseek_tiny
from tpusystem.models.nemotron_h import NemotronH, nemotron_tiny
from tpusystem.models.ouro import Ouro, ouro_tiny
from tpusystem.models.dots3 import Dots3, dots3_tiny
from tpusystem.models.resnet import ResNet, resnet50, resnet_tiny
from tpusystem.models.dlrm import (DLRM, TwoTower, dlrm_tiny, two_tower_tiny)

__all__ = ['MLP', 'GPT2', 'GPT2Pipelined', 'gpt2_small', 'gpt2_tiny',
           'Llama', 'llama3_8b', 'llama_tiny',
           'DeepSeekV2', 'deepseek_tiny', 'NemotronH', 'nemotron_tiny',
           'Ouro', 'ouro_tiny', 'Dots3', 'dots3_tiny',
           'ResNet', 'resnet50', 'resnet_tiny',
           'DLRM', 'TwoTower', 'dlrm_tiny', 'two_tower_tiny']
