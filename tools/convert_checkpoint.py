"""Convert a reference-format npz weights archive into per-stage Orbax
checkpoints (one directory per pipeline stage), for `runtime.py --comm dcn
--stage-ckpt`: each rank then restores exactly its own stage shard.

Usage:
    python tools/convert_checkpoint.py -m MODEL -M weights.npz \
        -pt 1,24,25,48 -o ckpts/
"""
import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pipeedge_tpu.models import registry  # noqa: E402
from pipeedge_tpu.utils import checkpoint as ckpt  # noqa: E402

logging.basicConfig(stream=sys.stdout, level=logging.INFO,
                    format="%(message)s")
logger = logging.getLogger(__name__)


def main():
    parser = argparse.ArgumentParser(
        description="npz -> per-stage Orbax checkpoints",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("-m", "--model-name", required=True,
                        choices=registry.get_model_names())
    parser.add_argument("-M", "--model-file", default=None,
                        help="npz weights (default: the model's default file)")
    parser.add_argument("-pt", "--partition", required=True,
                        help="comma-delimited layer pairs, e.g. '1,24,25,48'")
    parser.add_argument("-o", "--output-dir", required=True)
    args = parser.parse_args()

    nums = [int(x) for x in args.partition.split(",")]
    assert len(nums) % 2 == 0, "partition must be layer pairs"
    partition = list(zip(nums[::2], nums[1::2]))
    npz = args.model_file or registry.get_model_default_weights_file(
        args.model_name)
    dirs = ckpt.save_stage_checkpoints(args.model_name, npz,
                                       args.output_dir, partition)
    for i, d in enumerate(dirs):
        logger.info("stage %d [%d, %d] -> %s", i, *partition[i], d)


if __name__ == "__main__":
    from pipeedge_tpu.utils import enable_compile_cache
    enable_compile_cache()
    main()
