"""DCGAN amp example — reference: examples/dcgan/main_amp.py.

The reference adapts pytorch/examples DCGAN to apex amp with TWO models and
TWO optimizers sharing loss scalers (its README calls out the
``amp.initialize([netD, netG], [optD, optG], num_losses=3)`` pattern). The
TPU version keeps that structure: one amp policy, separate AmpStates for D
and G, three logical losses (errD_real, errD_fake, errG), synthetic data.

Run:  python examples/dcgan/main_amp.py --iters 20 --opt-level O2
"""

import os as _os
import sys as _sys

_REPO_ROOT = _os.path.abspath(_os.path.join(_os.path.dirname(__file__),
                                            _os.pardir, _os.pardir))
if _REPO_ROOT not in _sys.path:
    _sys.path.insert(0, _REPO_ROOT)

import argparse
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from apex_tpu import amp


class Generator(nn.Module):
    """DCGAN G: project + 3 transposed convs (reference netG, trimmed)."""
    feat: int = 32

    @nn.compact
    def __call__(self, z):
        x = nn.Dense(4 * 4 * self.feat * 4)(z)
        x = x.reshape(z.shape[0], 4, 4, self.feat * 4)
        for mult in (2, 1):
            x = nn.ConvTranspose(self.feat * mult, (4, 4), strides=(2, 2),
                                 padding="SAME")(x)
            x = nn.GroupNorm(num_groups=8)(x)
            x = nn.relu(x)
        x = nn.ConvTranspose(3, (4, 4), strides=(2, 2), padding="SAME")(x)
        return jnp.tanh(x)  # 32x32x3


class Discriminator(nn.Module):
    """DCGAN D: 3 strided convs + head (reference netD, trimmed)."""
    feat: int = 32

    @nn.compact
    def __call__(self, x):
        for mult in (1, 2, 4):
            x = nn.Conv(self.feat * mult, (4, 4), strides=(2, 2),
                        padding="SAME")(x)
            x = nn.leaky_relu(x, 0.2)
        x = x.reshape(x.shape[0], -1)
        return nn.Dense(1)(x)[:, 0]


def bce_logits(logits, target):
    logits = jnp.asarray(logits, jnp.float32)
    return optax.sigmoid_binary_cross_entropy(
        logits, jnp.full_like(logits, target)).mean()


def main(argv=None):
    p = argparse.ArgumentParser(description="apex_tpu DCGAN amp example")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("-b", "--batch-size", type=int, default=32)
    p.add_argument("--nz", type=int, default=64)
    p.add_argument("--lr", type=float, default=2e-4)
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--loss-scale", default="dynamic")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    policy = amp.resolve_policy(opt_level=args.opt_level,
                                loss_scale=args.loss_scale)
    print(policy.banner())

    netG, netD = Generator(), Discriminator()
    rng = jax.random.PRNGKey(args.seed)
    kG, kD, rng = jax.random.split(rng, 3)
    z0 = jnp.zeros((2, args.nz))
    x0 = jnp.zeros((2, 32, 32, 3))
    paramsG = netG.init(kG, z0)["params"]
    paramsD = netD.init(kD, x0)["params"]

    adam = optax.adam(args.lr, b1=0.5, b2=0.999)

    # D step: real + fake losses (the reference's errD_real/errD_fake are
    # loss ids 0 and 1 of num_losses=3)
    def lossD(pD, batch):
        real, fake = batch
        errD_real = bce_logits(netD.apply({"params": pD}, real), 1.0)
        errD_fake = bce_logits(netD.apply({"params": pD}, fake), 0.0)
        return errD_real + errD_fake

    # G step: fool D through frozen D params (loss id 2)
    def lossG(pG, batch):
        z, pD = batch
        fake = netG.apply({"params": pG}, z)
        return bce_logits(netD.apply({"params": pD}, fake), 1.0)

    initD, stepD = amp.make_train_step(lossD, adam, policy)
    initG, stepG = amp.make_train_step(lossG, adam, policy)
    stateD, stateG = initD(paramsD), initG(paramsG)
    jitD = jax.jit(stepD)
    jitG = jax.jit(stepG)
    jit_gen = jax.jit(lambda pG, z: netG.apply({"params": pG}, z))

    t0 = None
    for it in range(args.iters):
        rng, kz, kx = jax.random.split(rng, 3)
        real = jax.random.uniform(kx, (args.batch_size, 32, 32, 3),
                                  minval=-1.0, maxval=1.0)
        z = jax.random.normal(kz, (args.batch_size, args.nz))
        fake = jit_gen(policy.cast_params(amp.master_params(stateG)), z)
        stateD, mD = jitD(stateD, (real, jax.lax.stop_gradient(fake)))
        stateG, mG = jitG(
            stateG, (z, policy.cast_params(amp.master_params(stateD))))
        if it == 2:
            mG["loss"].block_until_ready()
            t0 = time.perf_counter()
        if it % 5 == 0 or it == args.iters - 1:
            print(f"[{it}/{args.iters}] loss_D {float(mD['loss']):.4f} "
                  f"loss_G {float(mG['loss']):.4f} "
                  f"scale {float(mD['loss_scale']):g}")
    if t0 is not None and args.iters > 3:
        dt = time.perf_counter() - t0
        print(f"{(args.iters - 3) * args.batch_size / dt:.1f} img/s")


if __name__ == "__main__":
    from apex_tpu.utils.chip import enable_compile_cache

    print(f"=> compile cache: {enable_compile_cache()}")
    main()
