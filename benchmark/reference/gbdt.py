"""Plain references for the GBDT cells. No code of the program is used.

``margins``: a numpy traversal of a LightGBM text model (the format
``save_model_string`` writes), numerical splits only: at an internal
node a row goes left when ``x[feature] <= threshold``; a negative child
``c`` is leaf ``~c``; a tree's output is its leaf's value and the
margin is the sum over trees plus the model's ``init_score`` trailer.

``reference_accuracy``: an independent histogram GBDT (scikit-learn's
``HistGradientBoostingClassifier``) at the same hyper-parameters and
tree count, fitted on a seeded sample of the training rows, scored on
the held-out rows.
"""

import numpy as np


def parse_model(text):
    """``(trees, init_score)``; a tree is a dict of numpy arrays."""
    trees, init_score, cur = [], 0.0, None
    for line in text.splitlines():
        if line.startswith("Tree="):
            cur = {}
            trees.append(cur)
        elif line.startswith("end of trees"):
            cur = None
        elif "=" in line:
            key, _, value = line.partition("=")
            if cur is not None and key in (
                    "split_feature", "threshold", "left_child",
                    "right_child", "leaf_value", "decision_type"):
                cur[key] = np.array(value.split(), dtype=(
                    np.float64 if key in ("threshold", "leaf_value")
                    else np.int64))
            elif cur is None and key == "init_score":
                init_score = float(value)
    return trees, init_score


def margins(text, x):
    trees, init_score = parse_model(text)
    x = np.asarray(x, np.float64)
    rows = np.arange(len(x))
    out = np.full(len(x), init_score, np.float64)
    for tree in trees:
        if "split_feature" not in tree:          # a single-leaf tree
            out += tree["leaf_value"][0]
            continue
        if np.any(tree["decision_type"] & 1):
            raise ValueError("reference traversal: categorical split")
        node = np.zeros(len(x), np.int64)
        while True:
            live = node >= 0
            if not live.any():
                break
            cur = node[live]
            go_left = (x[rows[live], tree["split_feature"][cur]]
                       <= tree["threshold"][cur])
            node[live] = np.where(go_left, tree["left_child"][cur],
                                  tree["right_child"][cur])
        out += tree["leaf_value"][~node]
    return out


def reference_accuracy(x, y, x_held, y_held, params, trees, sample_rows,
                       seed):
    from sklearn.ensemble import HistGradientBoostingClassifier

    rng = np.random.default_rng(seed)
    take = (rng.choice(len(x), size=sample_rows, replace=False)
            if sample_rows < len(x) else np.arange(len(x)))
    ref = HistGradientBoostingClassifier(
        max_iter=trees, max_leaf_nodes=params["numLeaves"],
        max_depth=params["maxDepth"], max_bins=params["maxBin"],
        min_samples_leaf=params["minDataInLeaf"],
        learning_rate=params.get("learningRate", 0.1),
        early_stopping=False, random_state=0)
    ref.fit(x[take], y[take])
    return float((ref.predict(x_held) == y_held).mean())
