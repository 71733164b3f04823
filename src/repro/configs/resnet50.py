"""ResNet-50 v1.5 @224 (ImageNet; torchvision ``resnet50``) — the
bottleneck ResNet that FPGA accelerators such as Xilinx's DPU report.

Not part of the LM arch pool; compiled and executed like resnet18.
"""
from repro.models.cnn import CNNConfig, reduced_config

CONFIG = CNNConfig(arch="resnet50", n_classes=1000, in_hw=224)
SMOKE = reduced_config("resnet50")
