"""The paper's own model family: large-scale sparse CTR models served by the
WeiPS parameter server — LR-FTRL, FM-FTRL, FM-SGD, DNN (paper §4.1.2:
"LR-FTRL has 3 sparse matrices, FM-FTRL has 6, FM-SGD has 2, DNN is multiple
sparse plus multiple dense matrices"), and DLRM-DCNv2 (MLPerf Training's
recommendation model: arXiv 1906.00091, 2008.13535), the demanding public
model of the same field: dense features through a bottom MLP, multi-hot
fields sum-pooled from 128-wide embeddings, low-rank DCN-V2 cross layers
and a top MLP.

Features are hashed into a huge sparse ID space; only touched rows exist on
the PS (row-addressable sparse tables, see core/ps.py).
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class CTRConfig:
    name: str = "weips-ctr"
    model_type: str = "fm"          # "lr" | "fm" | "dnn" | "dlrm_dcnv2"
    feature_space: int = 2 ** 22    # hashed sparse feature ID space
    fields: int = 32                # feature fields per example
    embed_dim: int = 8              # FM latent dim / DNN embedding dim
    dnn_hidden: tuple[int, ...] = (128, 64)
    optimizer: str = "ftrl"         # "ftrl" | "sgd" | "adagrad" | "adam"
    # FTRL hyper-parameters (McMahan 2011)
    ftrl_alpha: float = 0.05
    ftrl_beta: float = 1.0
    ftrl_l1: float = 1.0
    ftrl_l2: float = 1.0
    lr: float = 0.05                # for sgd/adagrad/adam variants
    # dense inputs and the DLRM-DCNv2 tower (0 / () where the model has none)
    dense_features: int = 0         # float features an event carries
    multi_hot: tuple[int, ...] = ()  # ids a field pools (sum); () = one id
    bottom_mlp: tuple[int, ...] = ()  # dense features -> embed_dim
    top_mlp: tuple[int, ...] = ()   # crossed features -> logit
    dcn_layers: int = 0             # low-rank DCN-V2 cross layers
    dcn_rank: int = 0
    # the dense tower's own optimizer ("" = the store's, which the sparse
    # groups always use), at ``lr``
    dense_optimizer: str = ""

    @property
    def id_slots(self) -> int:
        """Ids an example carries: the multi-hot slots, else one a field."""
        return sum(self.multi_hot) if self.multi_hot else self.fields


LR_FTRL = CTRConfig(name="weips-lr-ftrl", model_type="lr", embed_dim=1,
                    optimizer="ftrl")
FM_FTRL = CTRConfig(name="weips-fm-ftrl", model_type="fm", optimizer="ftrl")
FM_SGD = CTRConfig(name="weips-fm-sgd", model_type="fm", optimizer="sgd")
DNN_ADAM = CTRConfig(name="weips-dnn-adam", model_type="dnn", optimizer="adam")
# MLPerf Training's DLRM-DCNv2 (recommendation_v2/torchrec_dlrm) at its
# published widths; the sparse tables train with WeiPS's FTRL-proximal, the
# tower with the reference's Adagrad. Fields and vocabulary are Criteo 1TB's.
DLRM_DCNV2 = CTRConfig(
    name="weips-dlrm-dcnv2", model_type="dlrm_dcnv2", feature_space=2 ** 26,
    fields=26, embed_dim=128, dense_features=13,
    multi_hot=(3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12,
               100, 27, 10, 3, 1, 1),
    bottom_mlp=(512, 256, 128), top_mlp=(1024, 1024, 512, 256, 1),
    dcn_layers=3, dcn_rank=512, optimizer="ftrl", dense_optimizer="adagrad",
    lr=0.005)

CTR_CONFIGS = {c.name: c for c in (LR_FTRL, FM_FTRL, FM_SGD, DNN_ADAM)}
