"""Image-patch collaboration (paper Sec. 4.2): 8 organizations each hold one
patch of every image; the CENTRAL patches carry the signal, and the gradient
assistance weights discover that (paper Fig. 4c interpretability claim).

Also demonstrates Deep Model Sharing (one extractor + per-round heads) and
round-resumable checkpointing.

Run: PYTHONPATH=src python examples/multi_org_images.py
"""
import tempfile

import numpy as np
import jax

from repro.checkpoint import GALCheckpoint
from repro.core import gal
from repro.core.gal import GALConfig
from repro.core.losses import get_loss
from repro.core.organizations import make_orgs
from repro.data.partition import flatten_for_tabular, split_image_patches
from repro.data.synthetic import make_patch_images, train_test_split
from repro.metrics.metrics import accuracy
from repro.models.zoo import ConvNet
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    ds = make_patch_images(rng, n=256, size=8, k=4, informative_center=True)
    train, test = train_test_split(ds, rng)
    xs = split_image_patches(train.x, 8)       # 2x4 grid; centre = {1,2,5,6}
    xs_te = split_image_patches(test.x, 8)

    model = ConvNet(widths=(8, 16), epochs=30)
    orgs = make_orgs(xs, model, dms=True)      # Deep Model Sharing
    loss = get_loss("xent")
    res = gal.fit(key, orgs, train.y, loss, GALConfig(rounds=3),
                  eval_sets={"test": (xs_te, test.y)}, metric_fn=accuracy)

    print("per-round test accuracy:",
          [f"{v:.1f}" for v in res.history["test_metric"]])
    w0 = np.asarray(res.weights[0])
    print("round-0 assistance weights (orgs 1..8):",
          [f"{v:.2f}" for v in w0])
    centre, border = w0[[1, 2, 5, 6]].sum(), w0[[0, 3, 4, 7]].sum()
    print(f"centre patches weight share: {centre:.2f} "
          f"(border: {border:.2f}) -> interpretable: {centre > border}")
    print(f"DMS: per-org extractors=1, heads={orgs[0].n_rounds_fit} "
          f"(T x memory saving vs per-round models)")

    # checkpoint the collaboration per round
    with tempfile.TemporaryDirectory() as d:
        ck = GALCheckpoint(d)
        for t, (eta, w) in enumerate(zip(res.etas, res.weights)):
            ck.save_round(t, eta, w, [None] * len(orgs))
        print(f"checkpointed rounds: 0..{ck.latest_round()} "
              f"(resume via GALCheckpoint.latest_round)")


if __name__ == "__main__":
    main()
