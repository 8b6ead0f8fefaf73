"""Bootstrap tool: emit the canonical markdown spec from a spec class.

Run once per fork to materialize ``specs/<fork>/beacon-chain.md`` with the
runtime's method sources as the embedded python blocks; from then on the
markdown is the editable source of truth and ``compiler.emit`` closes the
loop back to an importable module (golden-tested for parity).
"""
import inspect
import os
import re
import textwrap

_SECTIONS = [
    ("Configuration and types", """
The spec class binds a **preset** (compile-time constants: list limits,
committee sizes) and a **config** (runtime parameters: fork epochs,
genesis settings) at construction, then builds every SSZ container with
the preset's dimensions baked in.  This is the same two-tier constant
split the wire format depends on.""",
     ["__init__", "_build_config"]),
    ("Containers", """
All beacon-chain containers.  Field order is consensus-critical: it fixes
both the serialized layout and every generalized index.""",
     ["_build_types", "_block_body_fields", "_state_fields"]),
    ("Math helpers", """
Integer math used across the transition.  `integer_squareroot` must floor
and must accept the full uint64 range.""",
     ["integer_squareroot", "xor", "bytes_to_uint64"]),
    ("Predicates", """
Validator/attestation predicates.  Exceptions raised anywhere below mean
the containing object is invalid.""",
     ["is_active_validator", "is_eligible_for_activation_queue",
      "is_eligible_for_activation", "is_slashable_validator",
      "is_slashable_attestation_data", "is_valid_indexed_attestation",
      "is_valid_merkle_branch"]),
    ("Shuffling and committees", """
The swap-or-not shuffle and everything derived from it.  Committee
membership for an epoch is fully determined by the seed, so it can be
computed one epoch ahead.""",
     ["compute_shuffled_index", "compute_proposer_index",
      "compute_committee"]),
    ("Time and domains", """
Slot/epoch arithmetic and the domain-separation scheme that keeps
signatures from one context unusable in another.""",
     ["compute_epoch_at_slot", "compute_start_slot_at_epoch",
      "compute_activation_exit_epoch", "compute_fork_data_root",
      "compute_fork_digest", "compute_domain", "compute_signing_root"]),
    ("State accessors", """
Read-only views over the state.  The active-index, proposer and
total-balance accessors memoize on the registry root — identical lookups
dominate block processing.""",
     ["get_current_epoch", "get_previous_epoch", "get_block_root",
      "get_block_root_at_slot", "get_randao_mix",
      "get_active_validator_indices", "get_validator_churn_limit",
      "get_seed", "get_committee_count_per_slot", "get_beacon_committee",
      "get_beacon_proposer_index", "get_total_balance",
      "get_total_active_balance", "get_domain", "get_indexed_attestation",
      "get_attesting_indices"]),
    ("State mutators", """
Balance arithmetic saturates at zero; exits are queued against the churn
limit; slashing burns a proportional penalty and rewards the reporter.""",
     ["increase_balance", "decrease_balance", "initiate_validator_exit",
      "slash_validator"]),
    ("Genesis", """
Bootstrapping from eth1 deposits.  The state becomes valid once enough
full-balance validators are active at the configured genesis time.""",
     ["initialize_beacon_state_from_eth1", "is_valid_genesis_state"]),
    ("State transition", """
The top-level transition: empty slots are processed one at a time (epoch
processing fires on boundaries), the proposer signature is checked, the
block is applied, and the resulting state root must match the block.
Signature checks inside one block batch into a single verification
dispatch — the framework's device-native hot path.""",
     ["state_transition", "verify_block_signature", "process_slots",
      "process_slot"]),
    ("Epoch processing", """
The ten end-of-epoch stages, in mandatory order.  Justification counts
attesting balance for the two FFG checkpoints; finalization applies the
2-of-3 voting rules over the last four epochs.""",
     ["process_epoch", "get_matching_source_attestations",
      "get_matching_target_attestations", "get_matching_head_attestations",
      "get_unslashed_attesting_indices", "get_attesting_balance",
      "process_justification_and_finalization",
      "weigh_justification_and_finalization"]),
    ("Rewards and penalties", """
Per-component deltas: source/target/head participation, proposer
inclusion rewards, and the inactivity leak that drains non-participants
whenever finality stalls.""",
     ["get_base_reward", "get_proposer_reward", "get_finality_delay",
      "is_in_inactivity_leak", "get_eligible_validator_indices",
      "get_attestation_component_deltas", "get_source_deltas",
      "get_target_deltas", "get_head_deltas", "get_inclusion_delay_deltas",
      "get_inactivity_penalty_deltas", "get_attestation_deltas",
      "process_rewards_and_penalties"]),
    ("Registry updates and slashings", """
Activation queueing under the churn limit, ejections, and the
proportional slashing penalty sweep.""",
     ["process_registry_updates", "process_slashings",
      "process_eth1_data_reset", "process_effective_balance_updates",
      "process_slashings_reset", "process_randao_mixes_reset",
      "process_historical_roots_update",
      "process_participation_record_updates"]),
    ("Block processing", """
Header checks, randao mixing, eth1 voting, then the five operation
lists.  Every assertion failure invalidates the whole block.""",
     ["process_block", "process_block_header", "process_randao",
      "process_eth1_data", "process_operations",
      "process_proposer_slashing", "process_attester_slashing",
      "process_attestation", "get_validator_from_deposit",
      "add_validator_to_registry", "apply_deposit", "process_deposit",
      "process_voluntary_exit"]),
]


def generate_markdown(spec_cls, fork: str, previous_fork=None) -> str:
    out = [f"# The {fork} beacon chain",
           "",
           f"<!-- fork: {fork} -->"]
    if previous_fork:
        out.append(f"<!-- previous_fork: {previous_fork} -->")
    out.append("""
This document is the canonical specification of the %s consensus runtime
of this framework.  The fenced python blocks ARE the implementation: the
spec compiler (`python -m consensus_specs_tpu.compiler`) assembles them
into the importable runtime, and the conformance suite runs against the
result.  Behavioral parity target: ethereum/consensus-specs v1.4.0-beta.7
(`specs/%s/beacon-chain.md` of the reference tree).
""" % (fork, fork))

    emitted = set()
    for title, prose, names in _SECTIONS:
        out.append(f"## {title}")
        out.append(textwrap.dedent(prose).strip())
        out.append("")
        for name in names:
            fn = spec_cls.__dict__.get(name)
            if fn is None:
                continue
            src = textwrap.dedent(inspect.getsource(fn))
            out.append(f"### `{name}`\n")
            out.append("```python")
            out.append(src.rstrip())
            out.append("```")
            out.append("")
            emitted.add(name)

    import types
    missing = [n for n, v in spec_cls.__dict__.items()
               if isinstance(v, types.FunctionType)
               and not n.startswith("__") and n not in emitted]
    if missing:
        raise RuntimeError(f"sections missing methods: {missing}")
    return "\n".join(out) + "\n"


_FORK_INTROS = {
    "altair": """Altair introduces sync committees (512-member rotating
committees whose aggregate signatures light clients follow),
participation-flag epoch accounting replacing pending attestations, and
inactivity-leak scores.""",
    "bellatrix": """Bellatrix (the Merge) embeds execution payloads into
beacon blocks: the ExecutionEngine protocol, merge-transition predicates
and terminal-PoW validation, plus updated slashing/inactivity quotients.""",
    "capella": """Capella activates withdrawals: a bounded sweep over the
registry pays out fully/partially withdrawable validators through the
execution payload, BLS-to-execution credential changes, and historical
summaries replacing the historical-roots accumulator.""",
    "deneb": """Deneb carries blob KZG commitments (EIP-4844) with
versioned hashes and data-availability checks, pins voluntary-exit
domains (EIP-7044), extends attestation inclusion windows (EIP-7045) and
caps the activation churn (EIP-7514).""",
}


def generate_delta_markdown(spec_cls, fork: str, previous_fork: str) -> str:
    """Delta document for a non-phase0 fork: every method the fork class
    itself defines (its diff over the previous fork), one section per
    member, in definition order."""
    import types
    out = [f"# The {fork} beacon chain",
           "",
           f"<!-- fork: {fork} -->",
           f"<!-- previous_fork: {previous_fork} -->",
           "",
           _FORK_INTROS.get(fork, "").strip(),
           "",
           f"""This document specifies {fork} as a delta over
{previous_fork}: the fenced python blocks below override or extend the
{previous_fork} runtime (fork inheritance; the reference gets the same
effect from markdown dict-merge).  Compiled by
`python -m consensus_specs_tpu.compiler`.""",
           "", "## Constants and re-exports", "",
           "Values inherited from the fork module's constant tables:", ""]
    import sys as _sys
    mod = _sys.modules[spec_cls.__module__]
    const_lines = []
    for name, member in spec_cls.__dict__.items():
        if isinstance(member, (types.FunctionType, property)) \
                or name.startswith("__") \
                or name in ("fork", "previous_fork"):
            continue
        if hasattr(mod, name):
            const_lines.append(f"{name} = {name}")
        elif isinstance(member, (bool, int, bytes, str)) \
                and not isinstance(member, type):
            const_lines.append(f"{name} = {member!r}")
        else:
            const_lines.append(f"{name} = {name}")
    if const_lines:
        out.append("```python")
        out.extend(const_lines)
        out.append("```")
    out.extend(["", "## Fork deltas", ""])
    for name, member in spec_cls.__dict__.items():
        if isinstance(member, property):
            member = member.fget  # getsource includes the @property line
        elif not isinstance(member, types.FunctionType) or \
                name.startswith("__"):
            continue
        src = textwrap.dedent(inspect.getsource(member))
        out.append(f"### `{name}`\n")
        out.append("```python")
        out.append(src.rstrip())
        out.append("```")
        out.append("")
    return "\n".join(out) + "\n"


def _module_import_header(mod) -> str:
    """The module's import statements (everything the embedded python
    blocks need at module scope), taken verbatim from its source."""
    out = []
    cont = False
    for line in inspect.getsource(mod).splitlines():
        if cont:
            out.append(line)
            cont = line.rstrip().endswith(("(", ",", "\\")) \
                and ")" not in line
        elif line.startswith(("import ", "from ")):
            out.append(line)
            cont = line.rstrip().endswith(("(", "\\"))
        elif re.match(r"^(def|class|@)", line):
            break
    return "\n".join(out).rstrip()


def generate_component_doc(fork: str, document: str, title: str,
                           intro: str, mixin_cls, module_members=(),
                           section_notes=None) -> str:
    """Markdown for an auxiliary spec document (fork choice, validator
    duties, light client, optimistic sync) whose python blocks are the
    REAL runtime sources: module-scope definitions (``Store`` etc.) carry
    a ``<!-- scope: module -->`` marker the compiler honors, and every
    mixin method becomes a class-body block of the compiled spec class
    (reference compiles the same documents per fork,
    ``pysetup/md_doc_paths.py:65-80``)."""
    import sys
    import types
    mod = sys.modules[mixin_cls.__module__]
    out = [f"# {title}", "",
           f"<!-- fork: {fork} -->",
           f"<!-- document: {document} -->", "",
           textwrap.dedent(intro).strip(), ""]

    out += ["## Module-scope definitions", """
These definitions live at module scope of the compiled spec (imports,
event-machine state holders, plain helpers); the compiler splices them
above the spec class.""", ""]
    header = _module_import_header(mod)
    blocks = [header] if header else []
    emitted = set()
    # every module-level CONSTANT, automatically: mixin methods reference
    # them as globals of the compiled module
    import ast
    mod_src = inspect.getsource(mod)
    mod_lines = mod_src.splitlines()
    for node in ast.parse(mod_src).body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and re.fullmatch(r"[A-Z_][A-Z0-9_]*", node.targets[0].id):
            blocks.append("\n".join(
                mod_lines[node.lineno - 1:node.end_lineno]).rstrip())
            emitted.add(node.targets[0].id)
    for name in module_members:
        if name in emitted:
            continue
        member = getattr(mod, name)
        if isinstance(member, (types.FunctionType, type)):
            src = textwrap.dedent(inspect.getsource(member))
        else:
            src = f"{name} = {member!r}"
        blocks.append(src.rstrip())
    out.append("<!-- scope: module -->")
    out.append("```python")
    out.append("\n\n\n".join(blocks))
    out.append("```")
    out.append("")

    out.append("## Spec methods")
    out.append("")
    section_notes = section_notes or {}
    emitted_methods = set()
    for name, member in mixin_cls.__dict__.items():
        if isinstance(member, property):
            member = member.fget
        elif isinstance(member, (staticmethod, classmethod)):
            member = member.__func__  # getsource keeps the decorator line
        elif not isinstance(member, types.FunctionType) \
                or name.startswith("__"):
            continue
        if name.startswith("__"):
            continue
        out.append(f"### `{name}`\n")
        if name in section_notes:
            out.append(textwrap.dedent(section_notes[name]).strip() + "\n")
        out.append("```python")
        out.append(textwrap.dedent(inspect.getsource(member)).rstrip())
        out.append("```")
        out.append("")
        emitted_methods.add(name)
    # completeness gate: a silently-dropped member kind would let the
    # compiled spec diverge from the runtime class
    missing = [n_ for n_, m in mixin_cls.__dict__.items()
               if callable(m) or isinstance(m, (staticmethod, classmethod,
                                                property))
               if not n_.startswith("__") and n_ not in emitted_methods
               and not isinstance(m, type)]
    if missing:
        raise RuntimeError(
            f"{mixin_cls.__name__}: members not emitted to markdown: "
            f"{missing}")
    return "\n".join(out) + "\n"


def generate_module_doc(mod, fork: str, document: str, title: str,
                        intro: str) -> str:
    """Markdown for a spec LIBRARY (polynomial commitments): every
    module member in definition order, all module-scope, compiled into a
    standalone module (the reference's polynomial-commitments.md is
    likewise a function library, not beacon-state methods)."""
    import types
    import ast
    src = inspect.getsource(mod)
    src_lines = src.splitlines()
    out = [f"# {title}", "",
           f"<!-- fork: {fork} -->",
           f"<!-- document: {document} -->", "",
           textwrap.dedent(intro).strip(), "",
           "## Module-scope definitions", "",
           "<!-- scope: module -->", "```python",
           _module_import_header(mod), "```", ""]

    for node in ast.parse(src).body:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            continue  # the header block carries these
        if isinstance(node, ast.Expr) and isinstance(node.value,
                                                     ast.Constant):
            continue  # module docstring
        start = node.lineno
        for deco in getattr(node, "decorator_list", []):
            start = min(start, deco.lineno)  # the '@' line
        segment = "\n".join(src_lines[start - 1:node.end_lineno]).rstrip()
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            name = node.name
        elif isinstance(node, ast.Assign) and node.targets \
                and isinstance(node.targets[0], ast.Name):
            name = node.targets[0].id
        elif isinstance(node, ast.AnnAssign) \
                and isinstance(node.target, ast.Name):
            name = node.target.id
        else:
            name = src_lines[node.lineno - 1].strip()[:40]
        out.append(f"### `{name}`\n")
        out.append("<!-- scope: module -->")
        out.append("```python")
        out.append(segment)
        out.append("```")
        out.append("")
    return "\n".join(out) + "\n"


def main():
    from consensus_specs_tpu.forks.phase0 import Phase0Spec
    from consensus_specs_tpu.forks.altair import AltairSpec
    from consensus_specs_tpu.forks.bellatrix import BellatrixSpec
    from consensus_specs_tpu.forks.capella import CapellaSpec
    from consensus_specs_tpu.forks.deneb import DenebSpec
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(repo, "specs", "phase0", "beacon-chain.md")
    _write_doc(path, generate_markdown(Phase0Spec, "phase0"))
    print(f"wrote {path}")
    for cls, fork, prev in ((AltairSpec, "altair", "phase0"),
                            (BellatrixSpec, "bellatrix", "altair"),
                            (CapellaSpec, "capella", "bellatrix"),
                            (DenebSpec, "deneb", "capella")):
        path = os.path.join(repo, "specs", fork, "beacon-chain.md")
        _write_doc(path, generate_delta_markdown(cls, fork, prev))
        print(f"wrote {path}")
    write_component_docs(repo)


def _write_doc(path: str, text: str) -> None:
    """Rename-atomic spec-document write: the markdown IS the source of
    truth the compiler reads back — a crash mid-regeneration must leave
    the old document, never a torn prefix the next ``make pyspec``
    silently compiles."""
    from consensus_specs_tpu.recovery.atomic import atomic_replace_bytes
    os.makedirs(os.path.dirname(path), exist_ok=True)
    atomic_replace_bytes(path, text.encode("utf-8"))


def write_component_docs(repo: str) -> None:
    """The auxiliary spec documents, generated with real runtime sources
    so the compiler can build them into the compiled ladder (reference
    equivalents: specs/phase0/{fork-choice,validator}.md,
    specs/altair/{validator.md,light-client/sync-protocol.md},
    specs/sync/optimistic.md, specs/deneb/polynomial-commitments.md)."""
    from consensus_specs_tpu.forks.fork_choice import ForkChoiceMixin
    from consensus_specs_tpu.forks.validator_guide import (
        ValidatorGuideMixin, SyncDutiesMixin)
    from consensus_specs_tpu.forks.light_client import LightClientMixin
    from consensus_specs_tpu.forks.optimistic_sync import OptimisticSyncMixin
    from consensus_specs_tpu.ops import kzg as kzg_mod

    docs = [
        ("phase0/fork-choice.md", generate_component_doc(
            "phase0", "fork-choice", "Phase0 fork choice", """
This document specifies the LMD-GHOST fork-choice rule (reference
parity target: `specs/phase0/fork-choice.md`).  A node maintains a
`Store` — its view of blocks, states, checkpoints and the latest votes —
and feeds it three kinds of events: clock ticks (`on_tick`), blocks
(`on_block`), and attestations (`on_attestation` /
`on_attester_slashing`).  `get_head` folds the accumulated votes over
the viable block tree to pick the canonical head; `get_proposer_head`
layers the proposer re-org policy on top.  Design differences from the
reference (same observable behavior): `get_ancestor` is iterative,
`filter_block_tree` walks an explicit stack over a per-call
parent->children index, and `checkpoint_states` is keyed by
`(epoch, root)` tuples because this framework's SSZ values are mutable.
""", ForkChoiceMixin,
            ("INTERVALS_PER_SLOT", "LatestMessage", "Store", "_ckpt_key"))),
        ("phase0/validator.md", generate_component_doc(
            "phase0", "validator", "Phase0 honest validator guide", """
Expected behavior of an honest validator (reference parity target:
`specs/phase0/validator.md`): committee assignment lookahead, proposal
and attestation signing, the eth1-data voting window, attestation
subnet selection and rotation (`compute_subscribed_subnets`),
aggregation duties (`is_aggregator`, aggregate-and-proof), and the
weak-subjectivity checkpoint rules every syncing node must enforce.
""", ValidatorGuideMixin)),
        ("altair/validator.md", generate_component_doc(
            "altair", "validator", "Altair honest validator duties", """
Sync-committee duties added by altair (reference parity target:
`specs/altair/validator.md`): per-slot sync committee messages, the
subnet partition (`compute_subnets_for_sync_committee`),
selection-proof based aggregation (`is_sync_committee_aggregator`),
contribution-and-proof construction, and folding collected
contributions into the block's `sync_aggregate`.
""", SyncDutiesMixin)),
        ("altair/light-client/sync-protocol.md", generate_component_doc(
            "altair", "sync-protocol", "Altair light-client sync protocol",
            """
Minimal light-client sync (reference parity target:
`specs/altair/light-client/sync-protocol.md`): a `LightClientStore`
tracks a finalized and an optimistic header plus the current/next sync
committees; updates are validated against the committee of the
attested period (`validate_light_client_update`), applied under the
2/3-supermajority and finality rules, and force-updated after a
timeout.  The full-node side derives bootstraps and updates from
finalized blocks (`create_light_client_bootstrap/update/...`); capella
and deneb extend the header with execution fields via upgrade helpers.
""", LightClientMixin, ("floorlog2",))),
        ("sync/optimistic.md", generate_component_doc(
            "bellatrix", "optimistic", "Optimistic sync", """
Optimistic sync (reference parity target: `specs/sync/optimistic.md`):
a beacon node may import bellatrix+ blocks whose execution payloads are
not yet validated, tracking them in an `OptimisticStore`.  A block is
optimistically importable once its justified ancestor is deep enough
(`is_optimistic_candidate_block`); INVALIDATED verdicts prune the
subtree, VALIDATED verdicts shrink the optimistic set.
""", OptimisticSyncMixin,
            ("SAFE_SLOTS_TO_IMPORT_OPTIMISTICALLY", "OptimisticStore"))),
        ("deneb/polynomial-commitments.md", generate_module_doc(
            kzg_mod, "deneb", "polynomial-commitments",
            "Deneb KZG polynomial commitments", """
The KZG commitment library behind deneb blob transactions (reference
parity target: `specs/deneb/polynomial-commitments.md`).  Scalars live
in the BLS12-381 scalar field; blobs are 4096 field elements evaluated
over a bit-reversed root-of-unity domain.  The hot paths (`g1_lincomb`
MSM, pairing checks) dispatch to the device kernels when JAX answers
and fall back to the host Pippenger/oracle implementations otherwise.
Compiled into `forks/compiled/polynomial_commitments.py`, which the
compiled deneb spec binds as its `_kzg` backend.
""")),
    ]
    for rel, text in docs:
        path = os.path.join(repo, "specs", rel)
        _write_doc(path, text)
        print(f"wrote {path}")


if __name__ == "__main__":
    main()
