"""Bottleneck training recipe (paper §III, Eqs. 3-4; §V hyperparams).

Stage 1: train the undercomplete AE alone (L_AE, backbone frozen,
         lr 5e-4, Adam — the paper's 50-epoch recipe at toy scale).
Stage 2: fine-tune everything end-to-end (L_task).
Reports the accuracy of the split model before/after each stage.

Run:  PYTHONPATH=src python examples/train_bottleneck.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import trained_vgg, vgg_test_accuracy
from repro.core import bottleneck as B
from repro.data.synthetic import toy_image_iter, toy_images


def split_acc(model, params, ae, cut):
    xs, ys = toy_images(256, hw=16, seed=777)
    fwd = jax.jit(lambda xb: B.split_forward(model, params, ae, cut, xb))
    return float((np.asarray(fwd(jnp.asarray(xs))).argmax(-1) == ys).mean())


def main():
    model, params = trained_vgg()
    base = vgg_test_accuracy(model, params)
    cut = model.cut_points()[5]
    print(f"backbone accuracy: {base:.3f}; splitting after layer {cut}")

    it = map(lambda t: (jnp.asarray(t[0]), jnp.asarray(t[1])),
             toy_image_iter(32, hw=16, seed=9))

    # random AE: how much does an untrained bottleneck hurt?
    f_shape = jax.eval_shape(
        lambda x: model.apply_range(params, x, 0, cut + 1),
        jax.ShapeDtypeStruct((1, 16, 16, 3), jnp.float32)).shape
    ae0 = B.init_bottleneck(jax.random.PRNGKey(0), f_shape[1:], rate=0.5)
    print(f"split acc, untrained AE:      {split_acc(model, params, ae0, cut):.3f}")

    # stage 1: Eq. 3
    ae, losses = B.train_bottleneck(model, params, cut, it, steps=350, lr=2e-3)
    print(f"stage 1 (L_AE): loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    print(f"split acc, trained AE:        {split_acc(model, params, ae, cut):.3f}")

    # stage 2: Eq. 4
    # Eq. 4 is an MSE-to-target; at toy scale the CE form of L_task is far
    # better conditioned (MSE-to-onehot flattens the logit ranking) — both
    # are implemented, we fine-tune with CE here
    params2, ae2, tlosses = B.finetune(model, params, ae, cut, it,
                                       steps=120, lr=2e-4, loss_kind="ce")
    print(f"stage 2 (L_task): loss {tlosses[0]:.4f} -> {tlosses[-1]:.4f}")
    print(f"split acc, after fine-tune:   {split_acc(model, params2, ae2, cut):.3f}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
