"""Serving example (deliverable b): prefill/decode disaggregation + MTP
speculative decoding on a trained smoke model. Trains briefly on a
predictable stream so the MTP module has learnable structure, then serves
batched requests and reports the paper's §2.3.3 acceptance metric.

Run:  PYTHONPATH=src python examples/serve_mtp_disagg.py

The serving stack is ctx-aware (docs/serving.md §5): ``ctx=None`` (the
default used here) is the zero-config single-device path. To shard the
same deployment over a device mesh, build a ``ParallelCtx`` and hand it
to the pools — e.g. with 8 devices::

    from repro.launch.mesh import make_mesh
    from repro.parallel import context as pctx_mod
    dctx = pctx_mod.ParallelCtx(mesh=make_mesh((1, 4), ("data", "model")),
                                dp_axes=("data",), moe_impl="ep_dedup")
    pctx = pctx_mod.ParallelCtx(mesh=make_mesh((2, 4), ("data", "model")),
                                dp_axes=("data",), moe_impl="ep_flat")
    Disaggregator(cfg, ..., ctx=dctx, prefill_ctx=pctx)   # cross-mesh

(or pass ``--mesh/--prefill-mesh`` to ``repro.launch.serve``).
"""
import dataclasses
import sys

sys.path.insert(0, "src")

import numpy as np

from repro.configs.base import get_config, smoke_config
from repro.data.pipeline import SyntheticCorpus
from repro.serve.disagg import Disaggregator
from repro.serve.engine import Request
from repro.train.trainer import Trainer, TrainConfig


class CyclicCorpus(SyntheticCorpus):
    """Deterministic mod-8 stream — MTP can learn t+2 exactly."""

    def batch_at(self, step):
        t = (np.arange(self.seq) + step) % 8
        toks = np.tile(t, (self.batch, 1)).astype(np.int32)
        labels = np.concatenate(
            [toks[:, 1:], np.full((self.batch, 1), -1, np.int32)], 1)
        return {"tokens": toks, "labels": labels}


def main():
    cfg = smoke_config(get_config("deepseek-v3-671b"))
    cfg = dataclasses.replace(
        cfg, vocab_size=64,
        moe=dataclasses.replace(cfg.moe, capacity_factor=4.0))

    print("training the MLA+MoE+MTP stack on a predictable stream...")
    tc = TrainConfig(peak_lr=5e-3, warmup=5, total_steps=80)
    tr = Trainer(cfg, tc, data=CyclicCorpus(64, 24, 4), global_batch=4,
                 seq_len=24)
    out = tr.run(60)
    print(f"  loss {out['history'][0]['loss']:.2f} -> "
          f"{out['history'][-1]['loss']:.2f}")

    print("serving with prefill/decode disaggregation + MTP drafts...")
    # ctx/prefill_ctx=None: single-device pools, the zero-config default
    # (see the module docstring for the meshed variant)
    dis = Disaggregator(cfg, params=tr.params, decode_slots=3, max_len=64,
                        prefill_ep=32, decode_ep=128, use_mtp=True,
                        ctx=None, prefill_ctx=None)
    for rid in range(6):
        prompt = ((np.arange(8) + rid) % 8).astype(np.int32)
        dis.submit(Request(rid, prompt, max_new=16))
    dis.run()
    st = dis.decode.stats
    acc = dis.decode.acceptance_rate()
    from repro.serve.speculative import SpecDecodeModel
    print(f"  decode steps={st['steps']} tokens={st['tokens']} "
          f"handoff={dis.handoff_bytes/1e6:.2f}MB")
    print(f"  MTP draft acceptance={acc:.2f} -> modeled TPS gain "
          f"{SpecDecodeModel(acceptance=acc, model_layers=cfg.num_layers).tps_multiplier:.2f}x "
          f"(paper: 80-90% -> ~1.8x)")


if __name__ == "__main__":
    main()
