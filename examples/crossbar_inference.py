"""Quantized CNN inference through the HURRY crossbar functional model.

    PYTHONPATH=src python examples/crossbar_inference.py --net resnet18

Runs the same network fp32 and through the bit-sliced 1-bit-cell crossbar
(int8, 9-bit ADC, optional read noise) and reports logit agreement — the
functional side of the paper's "~1.86% accuracy drop" claim (§IV-B2).
"""

import argparse

import jax
import jax.numpy as jnp

from repro.api import GRAPHS
from repro.core.crossbar import CrossbarConfig, make_crossbar_matmul


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", default="alexnet",
                    choices=["alexnet", "vgg16", "resnet18"])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--noise", type=float, default=0.3,
                    help="thermal read-noise sigma (analog counts)")
    args = ap.parse_args()

    graph = GRAPHS[args.net]()
    params = graph.init_params(jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(0), graph.input_shape(args.batch))

    y_fp = graph.forward(params, x, logits=True)
    for label, cfg in [
            ("int8 crossbar (clean)", CrossbarConfig()),
            (f"int8 crossbar (noise={args.noise})",
             CrossbarConfig(noise_sigma_thermal=args.noise))]:
        mm = make_crossbar_matmul(cfg, noise_key=jax.random.PRNGKey(9))
        y_xb = graph.forward(params, x, mm=mm, logits=True)
        agree = float((jnp.argmax(y_fp, 1) == jnp.argmax(y_xb, 1)).mean())
        rel = float(jnp.linalg.norm(y_xb - y_fp) / jnp.linalg.norm(y_fp))
        print(f"{args.net:9s} {label:28s} argmax-agree {agree:6.1%}  "
              f"logit rel-err {rel:.3f}")


if __name__ == "__main__":
    main()
