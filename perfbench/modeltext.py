"""Reads the trees out of the model text that ``Booster.model_to_string``
writes (LightGBM's own v2 text format): the public output of a training
run, and the only thing of the program's that the comparison looks at
besides its final scores."""
import numpy as np

_INT = ("split_feature", "decision_type", "left_child", "right_child",
        "leaf_count", "internal_count")
_FLOAT = ("split_gain", "threshold", "leaf_value", "internal_value")


def parse(text):
    """[{key: array}] per tree, in boosting order."""
    trees = []
    for block in text.split("\nTree=")[1:]:
        kv = {}
        for line in block.splitlines():
            if "=" in line:
                k, v = line.split("=", 1)
                kv[k.strip()] = v
            elif line.strip() == "" and "shrinkage" in kv:
                break
        t = {"num_leaves": int(kv["num_leaves"]),
             "num_cat": int(kv.get("num_cat", "0")),
             "shrinkage": float(kv.get("shrinkage", "1"))}
        for k in _INT:
            t[k] = np.array(kv.get(k, "").split(), dtype=np.int64)
        for k in _FLOAT:
            t[k] = np.array(kv.get(k, "").split(), dtype=np.float64)
        trees.append(t)
    return trees
