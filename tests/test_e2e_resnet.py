"""Minimum end-to-end slice (SURVEY.md §7 step 5):
ResNet on CIFAR-10-like data, eager + compiled, loss must descend."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.io import DataLoader
from paddle_tpu.jit import TrainStep
from paddle_tpu.vision.datasets import Cifar10
from paddle_tpu.vision.models import resnet18, resnet50


def test_resnet50_forward():
    m = resnet50(num_classes=10)
    m.eval()
    out = m(paddle.randn([2, 3, 32, 32]))
    assert out.shape == [2, 10]


def test_resnet18_train_loss_descends():
    paddle.seed(42)
    np.random.seed(42)
    m = resnet18(num_classes=10)
    m.train()
    opt = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                             parameters=m.parameters())
    loss_fn = nn.CrossEntropyLoss()
    step = TrainStep(m, loss_fn, opt)

    # tiny fixed batch — overfit it
    X = paddle.randn([16, 3, 32, 32])
    Y = paddle.to_tensor(np.random.randint(0, 10, 16).astype(np.int64))
    losses = [float(step(X, Y).item()) for _ in range(12)]
    assert losses[-1] < losses[0], losses


def test_dataloader_with_cifar_synthetic():
    ds = Cifar10(mode="test")
    dl = DataLoader(ds, batch_size=32, shuffle=True, drop_last=True)
    xb, yb = next(iter(dl))
    assert xb.shape == [32, 3, 32, 32]
    assert yb.shape == [32]
    assert len(dl) == len(ds) // 32
