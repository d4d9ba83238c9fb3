"""Dataset registry: fmnist / cifar10 / fedemnist / synthetic.

Reference: `get_datasets` (src/utils.py:95-124) loads FashionMNIST/CIFAR-10 via
torchvision (with fixed normalization constants) and Fed-EMNIST from
pre-serialized `.pt` files. This environment has no torchvision and zero
egress, so we read the standard on-disk formats directly when present
(torchvision's own raw layout for FMNIST, the python pickle batches for
CIFAR-10, `torch.load` for Fed-EMNIST) and otherwise fall back to a
deterministic, class-structured **synthetic** dataset with identical shapes —
separable enough that FL training, backdoor attack and RLR-defense dynamics
are all exercised end-to-end.

Images are kept as *raw* pixels (uint8 for fmnist/cifar10, pre-normalized
float32 for fedemnist) because poisoning stamps raw pixels before
normalization (src/utils.py:169-177; SURVEY.md 2.3.4). Normalization happens
on-device in the train/eval step using the reference's constants
(src/utils.py:101, src/utils.py:113-116).
"""

from __future__ import annotations

import dataclasses
import gzip
import os
import pickle
import struct
from typing import Optional, Tuple

import numpy as np

from defending_against_backdoors_with_robust_learning_rate_tpu.data.arrays import (
    AgentShards)

# reference normalization constants (src/utils.py:101, 113-116)
NORM_STATS = {
    "fmnist": ((0.2860,), (0.3530,)),
    "cifar10": ((0.4914, 0.4822, 0.4465), (0.2023, 0.1994, 0.2010)),
    "fedemnist": ((0.0,), (1.0,)),   # inputs already normalized in the .pt files
    "synthetic": ((0.5,), (0.5,)),
}


@dataclasses.dataclass
class RawDataset:
    images: np.ndarray     # [N, H, W, C] raw pixels
    labels: np.ndarray     # [N] int32
    name: str

    def __len__(self) -> int:
        return len(self.labels)


@dataclasses.dataclass
class FederatedData:
    """Everything the FL loop needs, fully materialized as numpy arrays."""
    train: "AgentShards"                 # poisoned agent-stacked train shards
    val_images: np.ndarray               # [Nv, H, W, C] clean validation
    val_labels: np.ndarray               # [Nv]
    pval_images: np.ndarray              # poisoned validation (backdoor metric)
    pval_labels: np.ndarray
    mean: np.ndarray                     # [C] normalization mean (of x/255)
    std: np.ndarray                      # [C]
    raw_is_normalized: bool              # fedemnist: skip /255 + mean/std
    synthetic: bool = False

    @property
    def nbytes(self) -> int:
        """Host bytes of the train shards and both eval sets."""
        return sum(int(a.nbytes) for a in (
            self.train.images, self.train.labels, self.train.sizes,
            self.val_images, self.val_labels, self.pval_images,
            self.pval_labels))


def _norm_arrays(data: str) -> Tuple[np.ndarray, np.ndarray]:
    mean, std = NORM_STATS[data]
    return (np.asarray(mean, np.float32), np.asarray(std, np.float32))


@dataclasses.dataclass
class CohortData(FederatedData):
    """FederatedData for the cohort-sampled population path (ISSUE 7).

    ``train`` holds a ZERO-client AgentShards whose arrays carry only the
    *shapes and dtypes* one cohort row has ([0, max_n, H, W, C] — zero
    bytes): everything downstream that reads shard geometry (model init,
    AOT avals, the host-mode byte check) works unchanged, while the
    actual population lives in the memory-mapped client bank. Cohort rows
    are materialized per round by ``gather_cohort`` — base-dataset fancy
    indexing through the bank's offset store, with corrupt clients'
    rows poisoned by the same per-client routine the dense build uses
    (attack/poison.poison_client_row: bitwise-identical shards)."""
    bank: object = None                  # data/bank.ClientBank
    base_images: np.ndarray = None       # [N, H, W, C] raw pixels
    base_labels: np.ndarray = None       # [N] int32
    max_n: int = 0                       # padded cohort-row length
    cfg: object = None                   # poison + population params
    _stamps: dict = dataclasses.field(default_factory=dict)

    def gather_cohort(self, ids) -> Tuple[np.ndarray, np.ndarray,
                                          np.ndarray]:
        """([m, max_n, ...], [m, max_n], [m]) padded stacks for the
        sampled cohort — O(cohort) work and memory, population-blind."""
        from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
            poison)
        imgs, lbls, sizes = self.bank.gather(ids, self.base_images,
                                             self.base_labels, self.max_n)
        cfg = self.cfg
        if cfg.num_corrupt > 0 and cfg.poison_frac > 0:
            for j, cid in enumerate(np.asarray(ids)):
                cid = int(cid)
                if cid >= cfg.num_corrupt:
                    continue
                stamp = self._stamps.get(cid)
                if stamp is None:
                    # attack-registry stamp source (attack/registry.py):
                    # static = the legacy per-agent stamp, dba = the
                    # agent's shard of the full pattern — same source as
                    # the dense build, so rows stay bitwise-identical
                    from defending_against_backdoors_with_robust_learning_rate_tpu.attack import (
                        registry as attack_registry)
                    stamp = attack_registry.stamp_for_agent(cfg, cid)
                    self._stamps[cid] = stamp
                poison.poison_client_row(imgs[j], lbls[j], int(sizes[j]),
                                         cid, cfg, stamp=stamp)
        return imgs, lbls, sizes


def resolve_bank_root(cfg) -> str:
    """The client-bank ROOT this config would use: --bank_dir wins;
    otherwise <data_dir>/client_banks when data_dir exists (persistent
    across runs, gitignored), else under log_dir (always writable).
    Shared with the chaos bank_corrupt drill (service/driver.py), which
    must search the same root the engine will open."""
    if cfg.bank_dir:
        return cfg.bank_dir
    base = (cfg.data_dir if os.path.isdir(cfg.data_dir) else cfg.log_dir)
    return os.path.join(base, "client_banks")


def resolve_bank_dir(cfg, key: str) -> str:
    if cfg.bank_dir:
        return cfg.bank_dir
    return os.path.join(resolve_bank_root(cfg), f"{cfg.data}-{key[:12]}")


def get_cohort_data(cfg) -> CohortData:
    """Build the cohort-sampled data environment: base dataset + client
    bank (opened when a matching build exists, partitioned once
    otherwise) + the usual eval sets. Host memory is O(base dataset), not
    O(population) — the bank is offset-indexed and memory-mapped."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack.poison import (
        build_poisoned_val)
    from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
        bank as bank_mod)

    train, val, synthetic = get_datasets(cfg)
    if isinstance(train, list):
        raise ValueError(
            f"cohort-sampled mode needs a single base dataset to index; "
            f"{cfg.data!r} loads pre-split per-user shards — run it "
            f"through the host-sampled path (--cohort_sampled off)")
    key = bank_mod.bank_key(
        train.labels, population=cfg.num_agents,
        partitioner=cfg.partitioner,
        samples_per_client=bank_mod.resolve_samples_per_client(
            cfg.samples_per_client, len(train.labels), cfg.num_agents),
        dirichlet_alpha=cfg.dirichlet_alpha,
        classes_per_client=cfg.classes_per_client, seed=cfg.seed,
        n_classes=cfg.n_classes)
    bank, built = bank_mod.get_or_build(
        resolve_bank_dir(cfg, key), train.labels,
        population=cfg.num_agents, partitioner=cfg.partitioner,
        samples_per_client=cfg.samples_per_client,
        dirichlet_alpha=cfg.dirichlet_alpha,
        classes_per_client=cfg.classes_per_client, seed=cfg.seed,
        n_classes=cfg.n_classes, shard_clients=cfg.bank_shard_clients,
        key=key, verify=cfg.bank_verify,
        workers=cfg.bank_build_workers)
    if not built:
        print(f"[bank] opened existing {cfg.partitioner} bank "
              f"({bank.population:,} clients) at {bank.dir}")
    max_n = bank.padded_max_n(cfg.bs)
    shard_shim = AgentShards(
        images=np.zeros((0, max_n) + train.images.shape[1:],
                        dtype=train.images.dtype),
        labels=np.zeros((0, max_n), dtype=np.int32),
        sizes=np.zeros((0,), dtype=np.int32))
    pv_imgs, pv_lbls = build_poisoned_val(val.images, val.labels, cfg)
    mean, std = _norm_arrays(cfg.data)
    return CohortData(
        train=shard_shim,
        val_images=val.images, val_labels=val.labels,
        pval_images=pv_imgs, pval_labels=pv_lbls,
        mean=mean, std=std,
        raw_is_normalized=(cfg.data == "fedemnist"),
        synthetic=synthetic,
        bank=bank, base_images=train.images, base_labels=train.labels,
        max_n=max_n, cfg=cfg)


# ---------------------------------------------------------------- loaders ---

def _read_idx(path: str) -> np.ndarray:
    """Parse an IDX file (optionally gzipped) — the raw MNIST-family format.
    numpy frombuffer is zero-copy over the payload."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        buf = f.read()
    zero, dtype_code, ndim = struct.unpack(">HBB", buf[:4])
    dims = struct.unpack(">" + "I" * ndim, buf[4:4 + 4 * ndim])
    return np.frombuffer(buf, dtype=np.uint8,
                         offset=4 + 4 * ndim).reshape(dims)


def _find(path_candidates) -> Optional[str]:
    for p in path_candidates:
        if os.path.exists(p):
            return p
    return None


def _load_fmnist(data_dir: str) -> Optional[Tuple[RawDataset, RawDataset]]:
    base_candidates = [
        os.path.join(data_dir, "FashionMNIST", "raw"),
        os.path.join(data_dir, "fmnist"),
        data_dir,
    ]
    out = []
    for split in ("train", "t10k"):
        img = lbl = None
        for base in base_candidates:
            img = _find([os.path.join(base, f"{split}-images-idx3-ubyte{s}")
                         for s in ("", ".gz")])
            lbl = _find([os.path.join(base, f"{split}-labels-idx1-ubyte{s}")
                         for s in ("", ".gz")])
            if img and lbl:
                break
        if not (img and lbl):
            return None
        images = _read_idx(img)[..., None]           # [N, 28, 28, 1] uint8
        labels = _read_idx(lbl).astype(np.int32)
        out.append(RawDataset(images, labels, "fmnist"))
    return out[0], out[1]


def _load_cifar10(data_dir: str) -> Optional[Tuple[RawDataset, RawDataset]]:
    base = _find([os.path.join(data_dir, "cifar-10-batches-py"),
                  os.path.join(data_dir, "cifar10", "cifar-10-batches-py")])
    if base is None:
        return None

    def load_batch(name):
        with open(os.path.join(base, name), "rb") as f:
            d = pickle.load(f, encoding="bytes")
        imgs = d[b"data"].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
        return imgs.astype(np.uint8), np.asarray(d[b"labels"], np.int32)

    tr_i, tr_l = zip(*[load_batch(f"data_batch_{i}") for i in range(1, 6)], strict=True)
    te_i, te_l = load_batch("test_batch")
    return (RawDataset(np.concatenate(tr_i), np.concatenate(tr_l), "cifar10"),
            RawDataset(te_i, te_l, "cifar10"))


def _to_numpy_pt(obj):
    """Best-effort extraction of (inputs, targets) from Fed-EMNIST .pt objects
    (the reference pickles H5Dataset-like objects, src/utils.py:11-36)."""
    import torch
    if isinstance(obj, dict) and "pixels" in obj:
        x, y = obj["pixels"], obj["label"]
    elif hasattr(obj, "inputs") and hasattr(obj, "targets"):
        x, y = obj.inputs, obj.targets
    elif isinstance(obj, (tuple, list)) and len(obj) == 2:
        x, y = obj
    else:
        raise ValueError(f"unrecognized .pt payload: {type(obj)}")
    x = x.numpy() if isinstance(x, torch.Tensor) else np.asarray(x)
    y = y.numpy() if isinstance(y, torch.Tensor) else np.asarray(y)
    x = np.asarray(x, np.float32)
    if x.ndim == 4 and x.shape[1] == 1:          # NCHW -> NHWC
        x = x.transpose(0, 2, 3, 1)
    elif x.ndim == 3:
        x = x[..., None]
    return x, y.astype(np.int32)


def _load_fedemnist(data_dir: str):
    """Returns (per_user_shards | None, val RawDataset) or None.

    Layout mirrors the reference (src/utils.py:106-109, src/agent.py:17):
      Fed_EMNIST/fed_emnist_all_valset.pt
      Fed_EMNIST/user_trainsets/user_{id}_trainset.pt
    """
    base = _find([os.path.join(data_dir, "Fed_EMNIST"),
                  os.path.join(data_dir, "fedemnist")])
    if base is None:
        return None
    import torch
    val_path = _find([os.path.join(base, "fed_emnist_all_valset.pt")])
    if val_path is None:
        return None
    vx, vy = _to_numpy_pt(torch.load(val_path, weights_only=False))
    users_dir = os.path.join(base, "user_trainsets")
    shards = []
    uid = 0
    while os.path.exists(os.path.join(users_dir, f"user_{uid}_trainset.pt")):
        ux, uy = _to_numpy_pt(torch.load(
            os.path.join(users_dir, f"user_{uid}_trainset.pt"),
            weights_only=False))
        shards.append((ux, uy))
        uid += 1
    return shards, RawDataset(vx, vy, "fedemnist")


# ------------------------------------------------------------- synthetic ---

def make_synthetic(name: str, shape: Tuple[int, int, int], n_train: int,
                   n_val: int, seed: int, n_classes: int = 10,
                   float_normalized: bool = False, hardness: float = 0.0
                   ) -> Tuple[RawDataset, RawDataset]:
    """Deterministic class-structured data: each class is a fixed random
    prototype image plus pixel noise — linearly separable, so a small CNN
    learns it in a few steps and backdoor dynamics are observable.

    `hardness` in [0, 1] controls task difficulty (VERDICT r1 #4: at 0 the
    task saturates val_acc=1.0 within ~20 rounds, which makes accuracy
    curves vacuous). At hardness h:
      - each sample's prototype is circularly shifted by a per-sample
        random offset up to round(6h) pixels per axis — template matching
        stops working and the CNN has to learn shift-tolerant features,
        which is what makes accuracy climb over tens of rounds instead of
        a few steps (a fixed template is linearly separable at any noise
        level, so noise alone cannot slow learning down),
      - each prototype is pulled toward a single shared background image
        (class signal shrinks by 1-0.85h — classes overlap),
      - pixel noise grows from sigma=0.10 to 0.10+0.35h (SNR drops),
      - a fraction 0.1h of TRAIN labels is resampled uniformly (irreducible
        label noise; validation stays clean so val_acc is interpretable).
    The trojan patterns are stamped AFTER generation on raw pixels
    (attack/poison.py), so the trigger stays at its fixed location — shifts
    make the task harder without touching the backdoor geometry.
    hardness=0 reproduces the round-1 data bit-for-bit."""
    rng = np.random.default_rng(seed)
    h, w, c = shape
    protos = rng.uniform(0.15, 0.85, size=(n_classes, h, w, c))
    if hardness > 0.0:
        shared = rng.uniform(0.15, 0.85, size=(h, w, c))
        mix = 0.85 * float(hardness)
        protos = (1.0 - mix) * protos + mix * shared
    sigma = 0.10 + 0.35 * float(hardness)
    label_noise = 0.1 * float(hardness)
    max_shift = int(round(6.0 * float(hardness)))

    def gen(n, split_seed, noisy_labels):
        r = np.random.default_rng(seed * 1000003 + split_seed)
        labels = r.integers(0, n_classes, size=n).astype(np.int32)
        x = protos[labels]
        if max_shift > 0:
            dy = r.integers(-max_shift, max_shift + 1, size=n)
            dx = r.integers(-max_shift, max_shift + 1, size=n)
            ry = (np.arange(h)[None, :] - dy[:, None]) % h        # [n, h]
            rx = (np.arange(w)[None, :] - dx[:, None]) % w        # [n, w]
            x = x[np.arange(n)[:, None, None],
                  ry[:, :, None], rx[:, None, :]]                 # [n,h,w,c]
        noise = r.normal(0.0, sigma, size=(n, h, w, c))
        x = np.clip(x + noise, 0.0, 1.0)
        if noisy_labels and label_noise > 0.0:
            flip = r.random(n) < label_noise
            labels = np.where(
                flip, r.integers(0, n_classes, size=n).astype(np.int32),
                labels)
        if float_normalized:
            return x.astype(np.float32), labels
        return (x * 255.0).astype(np.uint8), labels

    tx, ty = gen(n_train, 1, True)
    vx, vy = gen(n_val, 2, False)
    return RawDataset(tx, ty, name), RawDataset(vx, vy, name)


# -------------------------------------------------------------- registry ---

def get_datasets(cfg) -> Tuple[object, RawDataset, bool]:
    """Return (train, val, synthetic?) where train is a RawDataset, or for
    fedemnist a list of per-user (images, labels) shards.

    Mirrors src/utils.py:95-124 with on-disk formats replacing torchvision.
    """
    if cfg.data == "fmnist":
        got = _load_fmnist(cfg.data_dir)
        if got is not None:
            return got[0], got[1], False
        tr, va = make_synthetic("fmnist", (28, 28, 1), cfg.synth_train_size,
                                cfg.synth_val_size, cfg.seed,
                                hardness=cfg.synth_hardness)
        return tr, va, True
    if cfg.data == "cifar10":
        got = _load_cifar10(cfg.data_dir)
        if got is not None:
            return got[0], got[1], False
        tr, va = make_synthetic("cifar10", (32, 32, 3), cfg.synth_train_size,
                                cfg.synth_val_size, cfg.seed,
                                hardness=cfg.synth_hardness)
        return tr, va, True
    if cfg.data == "fedemnist":
        got = _load_fedemnist(cfg.data_dir)
        if got is not None:
            shards, val = got
            if len(shards) < cfg.num_agents:
                raise ValueError(
                    f"fedemnist: found only {len(shards)} contiguous "
                    f"user_<id>_trainset.pt shards under {cfg.data_dir!r} but "
                    f"--num_agents={cfg.num_agents}; refusing to train with "
                    f"out-of-range agent ids")
            return shards[:cfg.num_agents], val, False
        # synthetic non-IID per-user shards, uneven sizes, float-normalized
        rng = np.random.default_rng(cfg.seed + 7)
        tr, va = make_synthetic("fedemnist", (28, 28, 1),
                                cfg.synth_train_size, cfg.synth_val_size,
                                cfg.seed, float_normalized=True,
                                hardness=cfg.synth_hardness)
        sizes = rng.integers(max(8, cfg.bs // 4),
                             max(16, cfg.bs), size=cfg.num_agents)
        order = rng.permutation(len(tr.images))
        shards, pos = [], 0
        for a in range(cfg.num_agents):
            n = int(min(sizes[a], len(order) - pos)) or 8
            idx = order[pos:pos + n] if pos + n <= len(order) else \
                rng.choice(len(tr.images), size=n)
            pos += n
            shards.append((tr.images[idx], tr.labels[idx]))
        return shards, va, True
    if cfg.data == "synthetic":
        tr, va = make_synthetic("synthetic", cfg.image_shape,
                                cfg.synth_train_size, cfg.synth_val_size,
                                cfg.seed, hardness=cfg.synth_hardness)
        return tr, va, True
    raise ValueError(f"unknown dataset {cfg.data!r}")


def get_federated_data(cfg) -> FederatedData:
    """Build the complete device-ready federated dataset:
    partition -> stack -> poison corrupt agents -> poisoned val set.

    Mirrors the setup phase of src/federated.py:33-56.
    """
    # partition + pack go through the native host runtime when available
    # (native/fl_host.cc via data/native.py), numpy otherwise — identical
    # outputs either way (tests/test_native.py)
    from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
        native)
    from defending_against_backdoors_with_robust_learning_rate_tpu.attack.poison import (
        poison_agent_shards, build_poisoned_val)
    from defending_against_backdoors_with_robust_learning_rate_tpu.obs import (
        spans)

    if cfg.data == "tokens":
        # the token task's generator and its backdoor (data/tokens.py)
        from defending_against_backdoors_with_robust_learning_rate_tpu.data import (
            tokens)
        return tokens.get_federated_tokens(cfg)

    with spans.span("setup/data/load_or_generate"):
        train, val, synthetic = get_datasets(cfg)

    # pad shards to a multiple of the batch size so the client's
    # [n_batches, bs] reshape is exact (fl/client.py)
    with spans.span("setup/data/partition"):
        if isinstance(train, list):     # fedemnist-style per-user shards
            shards = native.pack_uneven([s[0] for s in train],
                                        [s[1] for s in train],
                                        pad_multiple=cfg.bs)
        else:
            groups = native.distribute_data(train.labels, cfg.num_agents,
                                            n_classes=cfg.n_classes)
            shards = native.pack_shards(train.images, train.labels, groups,
                                        cfg.num_agents, pad_multiple=cfg.bs)

    with spans.span("setup/data/poison"):
        imgs, lbls, pmask = poison_agent_shards(shards.images, shards.labels,
                                                shards.sizes, cfg)
    shards.images, shards.labels, shards.poison_mask = imgs, lbls, pmask

    with spans.span("setup/data/poisoned_val"):
        pv_imgs, pv_lbls = build_poisoned_val(val.images, val.labels, cfg)
    mean, std = _norm_arrays(cfg.data)
    return FederatedData(
        train=shards,
        val_images=val.images, val_labels=val.labels,
        pval_images=pv_imgs, pval_labels=pv_lbls,
        mean=mean, std=std,
        raw_is_normalized=(cfg.data == "fedemnist"),
        synthetic=synthetic,
    )
