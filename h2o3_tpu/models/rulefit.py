"""RuleFit — rules from tree ensembles + sparse linear model.

Reference: hex/rulefit/RuleFit.java:36 (~1.6K LoC) — trains tree models
at depths min_rule_length..max_rule_length, decomposes every path
root→leaf into a rule (conjunction of splits), builds a 0/1 rule matrix
plus winsorized linear terms, and fits an L1 GLM over it; output is the
rule importance table (RuleFitModel "rule_importance").

TPU redesign: rules are NOT evaluated per-condition — each tree is
routed once on device (the same static-depth routing loop as scoring,
models/tree.py), giving final leaf ids [N]; a rule's membership is
``lo <= nid < hi`` for the leaf-range its (possibly shallow) node covers
in the complete tree. The rule matrix assembles from T routed columns,
and the sparse GLM reuses the einsum-Gram IRLS/ADMM machinery.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from h2o3_tpu.frame.binning import rebin_for_scoring
from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.models.model import Model, ModelBuilder, ModelCategory, infer_category
from h2o3_tpu.models.tree import _route
from h2o3_tpu.utils.log import get_logger

log = get_logger("h2o3_tpu.rulefit")


def _extract_rules(forest, tree_idx: int, D: int) -> List[dict]:
    """Walk one complete tree (host) → rules with leaf-id ranges.

    Conds are (feat, thresh, na_left, side, binset): binset is None for
    numeric range splits, else the frozenset of bin ids going left
    (categorical subset split)."""
    feat = np.asarray(forest.feat[tree_idx])
    thresh = np.asarray(forest.thresh[tree_idx])
    na_left = np.asarray(forest.na_left[tree_idx])
    is_split = np.asarray(forest.is_split[tree_idx])
    cat_split = np.asarray(forest.cat_split[tree_idx])
    left_words = np.asarray(forest.left_words[tree_idx])
    rules: List[dict] = []

    def _binset(d, idx):
        if not bool(cat_split[d, idx]):
            return None
        words = left_words[d, idx]
        return frozenset(
            int(32 * k + b) for k in range(words.shape[0])
            for b in range(32) if (int(words[k]) >> b) & 1)

    def walk(d, idx, conds):
        if d == D or not is_split[d, idx]:
            if conds:
                span = 2 ** (D - d)
                rules.append({"tree": tree_idx, "conds": list(conds),
                              "lo": idx * span, "hi": (idx + 1) * span})
            return
        f, t, nal = int(feat[d, idx]), int(thresh[d, idx]), bool(na_left[d, idx])
        bs = _binset(d, idx)
        walk(d + 1, 2 * idx, conds + [(f, t, nal, "left", bs)])
        walk(d + 1, 2 * idx + 1, conds + [(f, t, nal, "right", bs)])

    walk(0, 0, [])
    return rules


def _rule_language(rule: dict, bm) -> str:
    """Human-readable rule string (reference Rule.languageRule)."""
    edges = np.asarray(bm.edges)
    parts = []
    for f, t, nal, side, binset in rule["conds"]:
        name = bm.names[f]
        if bm.is_cat[f]:
            dom = bm.domains[f] or []
            card = max(len(dom), 1)
            nbf = int(np.asarray(bm.nbins)[f])
            div = -(-card // nbf) if card > nbf else 1
            if binset is not None:
                levels = [dom[i] for i in range(len(dom))
                          if (i // div) in binset]
            else:
                levels = [dom[i] for i in range(len(dom))
                          if (i // div) <= t]
            s = (f"{name} in {{{', '.join(levels)}}}" if side == "left"
                 else f"{name} not in {{{', '.join(levels)}}}")
        else:
            v = float(edges[f, t]) if t < edges.shape[1] else float("inf")
            s = f"{name} < {v:.6g}" if side == "left" else f"{name} >= {v:.6g}"
        if (side == "left") == nal:
            s += " or NA"
        parts.append(s)
    return " & ".join(parts)


class RuleFitModel(Model):
    algo = "rulefit"

    def __init__(self, params, output, glm_model, tree_models: List,
                 rules: List[dict], linear_cols: List[str],
                 winsor: Dict[str, tuple]):
        super().__init__(params, output)
        self.glm_model = glm_model
        self.tree_models = tree_models   # per-depth GBMModels (forest + bm)
        self.rules = rules               # each: tree-model idx, tree, lo/hi
        self.linear_cols = linear_cols
        self.winsor = winsor

    def _feature_frame(self, frame: Frame) -> Frame:
        cols: Dict[str, np.ndarray] = {}
        ri = 0
        for mi, tm in enumerate(self.tree_models):
            bm = rebin_for_scoring(tm.bm, frame)
            B = bm.nbins_total
            D = tm.forest.feat.shape[1]
            my_rules = [r for r in self.rules if r["model"] == mi]
            by_tree: Dict[int, List[dict]] = {}
            for r in my_rules:
                by_tree.setdefault(r["tree"], []).append(r)
            for t, rl in sorted(by_tree.items()):
                tree = type(tm.forest)(*(a[t] for a in tm.forest))
                nid = np.asarray(_route(tree, bm.bins, B))[: frame.nrows]
                for r in rl:
                    cols[r["name"]] = ((nid >= r["lo"]) & (nid < r["hi"])
                                       ).astype(np.float64)
        for n in self.linear_cols:
            v = frame.col(n).to_numpy()
            lo, hi = self.winsor[n]
            cols[f"linear.{n}"] = np.clip(v, lo, hi)
        return Frame.from_numpy(cols)

    def _score_raw(self, frame: Frame) -> Dict[str, np.ndarray]:
        return self.glm_model._score_raw(self._feature_frame(frame))

    def model_performance(self, frame: Frame):
        ff = self._feature_frame(frame)
        y = self.output["response"]
        ff.add_column(frame.col(y))
        return self.glm_model.model_performance(ff)

    @property
    def rule_importance(self) -> List[dict]:
        return self.output["rule_importance"]


class RuleFitEstimator(ModelBuilder):
    """h2o-py H2ORuleFitEstimator surface
    (h2o-py/h2o/estimators/rulefit.py)."""

    algo = "rulefit"

    DEFAULTS = dict(
        seed=-1, algorithm="auto", min_rule_length=3, max_rule_length=3,
        max_num_rules=-1, model_type="rules_and_linear",
        rule_generation_ntrees=50, distribution="auto",
        sample_rate=0.8, nfolds=0, fold_assignment="auto",
        weights_column=None, fold_column=None, ignored_columns=None,
        lambda_=None,
    )

    def __init__(self, **params):
        merged = dict(self.DEFAULTS)
        if "Lambda" in params:
            params["lambda_"] = params.pop("Lambda")
        unknown = set(params) - set(merged)
        if unknown:
            raise ValueError(f"unknown RuleFit params: {sorted(unknown)}")
        merged.update(params)
        super().__init__(**merged)

    def _fit(self, frame: Frame, x: Sequence[str], y: Optional[str],
             job, validation_frame: Optional[Frame] = None) -> Model:
        from h2o3_tpu.models.gbm import GBMEstimator
        from h2o3_tpu.models.drf import DRFEstimator
        from h2o3_tpu.models.glm import GLMEstimator
        p = self.params
        category = infer_category(frame, y)
        if category == ModelCategory.MULTINOMIAL:
            raise ValueError("RuleFit: multinomial not supported yet")
        seed = int(p["seed"]) if int(p["seed"]) >= 0 else 0xBEEF
        model_type = str(p["model_type"])
        use_rules = "rules" in model_type
        use_linear = "linear" in model_type

        depths = list(range(int(p["min_rule_length"]),
                            int(p["max_rule_length"]) + 1))
        ntrees_each = max(1, int(p["rule_generation_ntrees"]) // max(len(depths), 1))
        algo = str(p["algorithm"]).lower()
        TreeEst = DRFEstimator if algo == "drf" else GBMEstimator

        tree_models, rules = [], []
        cols: Dict[str, np.ndarray] = {}
        if use_rules:
            for di, depth in enumerate(depths):
                kw = dict(ntrees=ntrees_each, max_depth=depth, seed=seed + di,
                          sample_rate=float(p["sample_rate"]))
                if TreeEst is GBMEstimator:
                    kw["learn_rate"] = 0.1
                tm = TreeEst(**kw).train(frame, y=y, x=list(x))
                tree_models.append(tm)
                K = tm.output.get("nclasses", 1)
                forest = tm.forest
                T = forest.feat.shape[0]
                D = forest.feat.shape[1]
                B = tm.bm.nbins_total
                # binomial GBM trains 1 tree/iter; trees stack plainly
                for t in range(T):
                    tree = type(forest)(*(a[t] for a in forest))
                    nid = np.asarray(_route(tree, tm.bm.bins, B))
                    for r in _extract_rules(forest, t, D):
                        r["model"] = di
                        r["name"] = f"M{di}T{t}N{r['lo']}"
                        r["lang"] = _rule_language(r, tm.bm)
                        mask = ((nid >= r["lo"]) & (nid < r["hi"])
                                )[: frame.nrows].astype(np.float64)
                        support = mask.mean()
                        if 0.0 < support < 1.0:
                            r["support"] = float(support)
                            rules.append(r)
                            cols[r["name"]] = mask
                job.update(0.5 / len(depths), f"rules depth {depth}")

        linear_cols: List[str] = []
        winsor: Dict[str, tuple] = {}
        if use_linear:
            for n in x:
                c = frame.col(n)
                if c.is_categorical or c.type == "string":
                    continue
                v = c.to_numpy()
                lo, hi = np.nanquantile(v, [0.025, 0.975])
                winsor[n] = (float(lo), float(hi))
                linear_cols.append(n)
                cols[f"linear.{n}"] = np.clip(v, lo, hi)

        if not cols:
            raise ValueError("RuleFit produced no features (no rules/linear)")
        ff = Frame.from_numpy(cols)
        ff.add_column(frame.col(y))

        lam = p["lambda_"]
        glm = GLMEstimator(
            family="binomial" if category == ModelCategory.BINOMIAL else "gaussian",
            alpha=1.0,
            lambda_=lam if lam is not None else None,
            lambda_search=lam is None, nlambdas=20,
            standardize=True,
            weights_column=p.get("weights_column"))
        gm = glm.train(ff, y=y, x=[n for n in ff.names if n != y])
        job.update(0.4, "glm fit")

        # rank rules by |coef|; enforce max_num_rules by zeroing the tail
        coefs = gm.coefficients
        max_rules = int(p["max_num_rules"])
        imp = []
        for r in rules:
            c = coefs.get(r["name"], 0.0)
            imp.append({"rule": r["lang"], "coefficient": float(c),
                        "support": r["support"], "name": r["name"]})
        for n in linear_cols:
            c = coefs.get(f"linear.{n}", 0.0)
            imp.append({"rule": f"linear({n})", "coefficient": float(c),
                        "support": 1.0, "name": f"linear.{n}"})
        imp.sort(key=lambda d: -abs(d["coefficient"]))
        if max_rules > 0:
            kill = {d["name"] for d in imp[max_rules:]}
            gm.coef = np.array(gm.coef)   # may be a read-only device view
            names = gm.output["coef_names"]
            for i, nm in enumerate(names):
                if nm in kill:
                    gm.coef[i] = 0.0
            imp = imp[:max_rules]
        imp = [d for d in imp if abs(d["coefficient"]) > 1e-12]

        output = {"category": category, "response": y, "names": list(x),
                  "domain": frame.col(y).domain,
                  "nclasses": frame.col(y).cardinality
                  if frame.col(y).is_categorical else 1,
                  "rule_importance": imp,
                  "n_rules": len(rules),
                  "default_threshold": gm.output.get("default_threshold", 0.5)}
        model = RuleFitModel(p, output, gm, tree_models,
                             [r for r in rules], linear_cols, winsor)
        model.training_metrics = gm.training_metrics
        if validation_frame is not None:
            model.validation_metrics = model.model_performance(validation_frame)
        return model
