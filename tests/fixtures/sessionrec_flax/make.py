"""Makes this directory's blobs. Run it ON THE COMMIT BEFORE PR 27 (b3cbcb9,
where ``SessionEncoder`` was a flax module), from that checkout's root:

    JAX_PLATFORMS=cpu python <here>/make.py <here>

``model.pkl``: a trained ``SessionRecModel`` as ``pio train`` stores it;
``ckpt/``: the trainer's checkpoint after epoch 1 of 2 (``checkpoint_dir``
"ckpt", relative); ``expected.json``: what that commit answered. ``tests/test_sessionrec_flax_tree.py`` loads them with the code
that stands.
"""

import json
import os
import pickle
import shutil
import sys

import numpy as np

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.sessionrec import (
    PreparedSequences, SessionRecAlgorithm, SessionRecParams)
from predictionio_tpu.parallel.mesh import MeshContext

PARAMS = dict(dim=16, heads=2, layers=2, max_len=8, epochs=2, batch_size=8,
              seed=5)
N_USERS, N_ITEMS, N_EVENTS = 20, 12, 160


def prepared() -> PreparedSequences:
    rng = np.random.default_rng(27)
    return PreparedSequences(
        user_ids=BiMap.from_vocab([f"u{i}" for i in range(N_USERS)]),
        item_ids=BiMap.from_vocab([f"i{i}" for i in range(N_ITEMS)]),
        user_idx=rng.integers(0, N_USERS, N_EVENTS),
        item_idx=rng.integers(0, N_ITEMS, N_EVENTS),
        times=np.arange(N_EVENTS, dtype=np.float64))


def main(out: str) -> None:
    from predictionio_tpu.ops.sessionrec import (
        SessionRecConfig, SessionRecTrainer)

    # the checkpoint's fingerprint covers the config, its directory too:
    # a relative one, so that a test can stand where it likes
    os.chdir(out)
    shutil.rmtree("ckpt", ignore_errors=True)
    pd = prepared()
    cfg = SessionRecConfig(**PARAMS, checkpoint_dir="ckpt")
    trainer = SessionRecTrainer((pd.user_idx, pd.item_idx, pd.times),
                                pd.n_users, pd.n_items, cfg)
    trainer.run(epochs=1)               # epoch 1 of the config's 2
    # the stored model: an uninterrupted train, no checkpoints
    algo = SessionRecAlgorithm(SessionRecParams(**PARAMS))
    model = algo.train(MeshContext(), pd)
    with open("model.pkl", "wb") as f:
        pickle.dump(model, f)
    queries = [{"items": ["i3", "i7", "i1"], "num": 4},
               {"user": "u2", "num": 4}]
    answers = [algo.predict(model, q) for q in queries]
    with open("expected.json", "w") as f:
        json.dump({"queries": queries, "answers": answers,
                   "losses": model.state.losses,
                   "first_epoch_loss": trainer._losses}, f, indent=1)


if __name__ == "__main__":
    main(os.path.abspath(sys.argv[1]))
