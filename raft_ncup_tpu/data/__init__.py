from raft_ncup_tpu.data.augment import (
    ColorJitter,
    FlowAugmentor,
    SparseFlowAugmentor,
    resize_sparse_flow_map,
)
from raft_ncup_tpu.data.datasets import (
    HD1K,
    KITTI,
    ArrayFlowDataset,
    FlowDataset,
    FlyingChairs,
    FlyingThings3D,
    MixedDataset,
    MpiSintel,
    fetch_training_set,
)
from raft_ncup_tpu.data.device_prefetch import DevicePrefetcher
from raft_ncup_tpu.data.loader import FlowLoader
from raft_ncup_tpu.data.synthetic import SyntheticFlowDataset

__all__ = [
    "ColorJitter",
    "FlowAugmentor",
    "SparseFlowAugmentor",
    "resize_sparse_flow_map",
    "FlowDataset",
    "ArrayFlowDataset",
    "FlyingChairs",
    "FlyingThings3D",
    "MpiSintel",
    "KITTI",
    "HD1K",
    "MixedDataset",
    "fetch_training_set",
    "DevicePrefetcher",
    "FlowLoader",
    "SyntheticFlowDataset",
]
