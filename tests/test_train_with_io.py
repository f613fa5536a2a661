"""Training with the real input pipeline (recordio -> ImageRecordIter ->
TrainStep) at CI scale.

Reference parity: the ``ImageRecordIter2`` + prefetcher + training-loop
composition (``src/io/iter_image_recordio_2.cc:715``,
``iter_prefetcher.h``).
"""
import os

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, recordio
from mxnet_tpu.gluon.model_zoo import vision


def test_train_step_from_image_record_iter(tmp_path):
    rec = str(tmp_path / "synth.rec")
    idx = str(tmp_path / "synth.idx")
    rs = onp.random.RandomState(0)
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    for i in range(32):
        img = rs.randint(0, 255, (64, 64, 3)).astype("uint8")
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i % 10), i, 0), img, quality=85))
    w.close()

    mx.np.random.seed(0)
    net = vision.resnet18_v1(classes=10)
    net.initialize()
    net(mx.np.zeros((8, 3, 64, 64)))
    opt = mx.optimizer.SGD(learning_rate=0.01, momentum=0.9)
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              opt, mesh=None)

    it = mx.io.ImageRecordIter(
        path_imgrec=rec, data_shape=(3, 64, 64), batch_size=8,
        shuffle=False, preprocess_threads=2, prefetch_buffer=2)
    it.reset()
    losses = []
    for _ in range(3):
        b = it.next()
        x = b.data[0]
        y = b.label[0].astype("int32")
        assert x.shape == (8, 3, 64, 64)
        losses.append(float(step(x, y)))
    assert all(onp.isfinite(l) for l in losses)
    # the same batch ordering decodes deterministically (shuffle=False):
    # first label of the first batch is record 0
    it.reset()
    b0 = it.next()
    assert float(b0.label[0][0]) == 0.0
