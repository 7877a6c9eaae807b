"""Model zoo mirroring the reference workload ladder (BASELINE.md):
MNIST MLP, ResNet-50, Transformer-base, BERT-base, DeepFM CTR, plus the
detection family (MobileNet-SSD, YOLOv3) exercising the detection zoo
through the IR.

Each builder constructs the IR into the current default programs and returns
the relevant vars; shapes/hyperparams follow the reference model configs
(e.g. /root/reference/python/paddle/fluid/tests/unittests/dist_mnist.py,
dist_se_resnext.py, dist_transformer.py, dist_ctr.py).
"""

from paddle_tpu.models.mlp import mnist_mlp
from paddle_tpu.models.resnet import resnet, resnet50
from paddle_tpu.models.transformer import transformer_encoder_model
from paddle_tpu.models.bert import bert_model
from paddle_tpu.models.deepfm import deepfm_model
from paddle_tpu.models.ssd import ssd_mobilenet
from paddle_tpu.models.yolov3 import yolov3
from paddle_tpu.models.vgg import vgg, vgg16
from paddle_tpu.models.se_resnext import se_resnext
from paddle_tpu.models.mellum2 import mellum2_model
from paddle_tpu.models.evabyte import evabyte_model
