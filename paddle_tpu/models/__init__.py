"""Model zoo.

Two API levels:
- JAX-native functional models (this package): pytree params with logical
  sharding axes (paddle_tpu.parallel.sharding), pure apply fns — the
  performance path used by benchmarks/, chip_smoke.py and
  __graft_entry__.py.
- Static-graph builders via paddle_tpu.layers for fluid-API parity live in
  each model file as `build_program_*` where applicable.

Models follow the reference's zoo: LeNet/MNIST (tests/book/
test_recognize_digits.py), ResNet-50 (test_dist_se_resnext lineage),
BERT-base (inference/tests/api/analyzer_bert_tester.cc), Transformer NMT
(test_dist_transformer.py).
"""

from . import bert, lenet, resnet, vgg  # noqa: F401
