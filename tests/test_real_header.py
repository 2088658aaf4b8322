"""Real-bytes Shelley header conformance (VERDICT r3 next-step 3).

The reference ships golden header bytes generated by the REAL Cardano
crypto stack (libsodium ECVRF + mlocked KES + Ed25519) in
`ouroboros-consensus-shelley-test/test/golden/ShelleyNodeToNodeVersion1/
Header` (built by `Test.Consensus.Shelley.Examples`: BHBody with
block 3 / slot 9, VRF certs over seeds blake2b(cbor(0)) / blake2b(cbor(1)),
OCert at counter 0 / period 0 — Examples.hs:439-458).  Parsing and fully
validating those bytes checks our whole header-crypto stack — proof
decoding, BOTH VRF verify equations, Sum6 KES over the exact body slice,
OCert Ed25519 — against externally-generated data, per backend.

The golden bytes are vendored (hex) so the suite runs without the
reference mounted; when it IS mounted we assert the vendored copy matches.
"""
import hashlib
import os

import pytest

from ouroboros_tpu.crypto.backend import CpuRefBackend
from ouroboros_tpu.eras import shelley_cbor as SC

GOLDEN_PATH = ("/root/reference/ouroboros-consensus-shelley-test/test/"
               "golden/ShelleyNodeToNodeVersion1/Header")
CARDANO_GOLDENS = [
    ("/root/reference/ouroboros-consensus-cardano-test/test/golden/"
     "CardanoNodeToNodeVersion3/Header_Shelley"),
    ("/root/reference/ouroboros-consensus-cardano-test/test/golden/"
     "CardanoNodeToNodeVersion3/Header_Allegra"),
]

# the example's VRF inputs: Seed(blake2b_256(toCBOR 0)) / (toCBOR 1)
# (Examples.hs:446-447,457-458 — mkBytes i = Seed(blake2b(cbor(i))))
ALPHA_NONCE = hashlib.blake2b(b"\x00", digest_size=32).digest()
ALPHA_LEADER = hashlib.blake2b(b"\x01", digest_size=32).digest()


def _load_golden() -> bytes:
    if os.path.exists(GOLDEN_PATH):
        return open(GOLDEN_PATH, "rb").read()
    return bytes.fromhex(VENDORED_HEX)


class TestParse:
    def test_parse_fields(self):
        hdr = SC.parse_header(_load_golden())
        b = hdr.body
        assert (b.block_no, b.slot) == (3, 9)
        assert len(b.prev_hash) == 32
        assert len(b.issuer_vkey) == 32 and len(b.vrf_vkey) == 32
        assert len(b.nonce_vrf.output) == 64
        assert len(b.nonce_vrf.proof) == 80
        assert len(b.ocert_sigma) == 64
        assert (b.protover_major, b.protover_minor) == (2, 0)
        assert len(hdr.kes_sig) == 448

    def test_reencode_byte_identical(self):
        raw = _load_golden()
        hdr = SC.parse_header(raw)
        assert hdr.to_wrapped_cbor() == raw
        # body re-encoded from fields is also byte-identical: the golden
        # encoding is canonical and so is ours
        assert hdr.body.to_cbor() == hdr.body_bytes

    def test_vendored_matches_reference(self):
        if not os.path.exists(GOLDEN_PATH):
            pytest.skip("reference not mounted")
        assert open(GOLDEN_PATH, "rb").read() == bytes.fromhex(VENDORED_HEX)

    @pytest.mark.parametrize("path", CARDANO_GOLDENS)
    def test_hfc_wrapped_goldens_parse_and_verify(self, path):
        if not os.path.exists(path):
            pytest.skip("reference not mounted")
        hdr = SC.parse_header(open(path, "rb").read())
        assert SC.validate_header(hdr, ALPHA_NONCE, ALPHA_LEADER,
                                  CpuRefBackend())


class TestFullValidation:
    """The complete PRTCL-rule crypto on the real bytes, per backend."""

    def test_ref_backend(self):
        hdr = SC.parse_header(_load_golden())
        assert SC.validate_header(hdr, ALPHA_NONCE, ALPHA_LEADER,
                                  CpuRefBackend())

    def test_cpp_backend(self):
        from ouroboros_tpu.crypto import cpp_backend
        if cpp_backend.shared_library() is None:
            pytest.skip("native toolchain unavailable")
        hdr = SC.parse_header(_load_golden())
        assert SC.validate_header(hdr, ALPHA_NONCE, ALPHA_LEADER,
                                  cpp_backend.CppBackend())

    @pytest.mark.device
    # slow: ~65s of composite tracing for this header's window shape;
    # the real-bytes crypto stays tier-1 via test_ref_backend above,
    # and jax-vs-cpu verdict parity is tier-1-gated by
    # test_served_replay.py::test_mixed_verdict_equals_reference
    @pytest.mark.slow
    def test_jax_backend(self):
        from ouroboros_tpu.crypto.jax_backend import JaxBackend
        hdr = SC.parse_header(_load_golden())
        assert SC.validate_header(hdr, ALPHA_NONCE, ALPHA_LEADER,
                                  JaxBackend(use_pallas=False,
                                             min_bucket=16))

    def test_rejects_tampered(self):
        hdr = SC.parse_header(_load_golden())
        # flip one bit of the leader proof
        bad_proof = bytes([hdr.body.leader_vrf.proof[0] ^ 1]) + \
            hdr.body.leader_vrf.proof[1:]
        from dataclasses import replace
        bad = SC.RealShelleyHeader(
            replace(hdr.body, leader_vrf=SC.VrfCert(
                hdr.body.leader_vrf.output, bad_proof)),
            hdr.kes_sig, replace(hdr.body, leader_vrf=SC.VrfCert(
                hdr.body.leader_vrf.output, bad_proof)).to_cbor())
        assert not SC.validate_header(bad, ALPHA_NONCE, ALPHA_LEADER,
                                      CpuRefBackend())
        # wrong KES evolution index
        assert not SC.validate_header(hdr, ALPHA_NONCE, ALPHA_LEADER,
                                      CpuRefBackend(),
                                      slots_per_kes_period=5)


VENDORED_HEX = (
    "d8185903e4828f03095820bb30a42c1e62f0afda5f0a4e8a562f7a13a24cea00ee81917b"
    "86b89e801314aa58208a88e3dd7409f195fd52db2d3cba5d72ca6709bf1d94121bf37488"
    "01b40f6f5c58208a88e3dd7409f195fd52db2d3cba5d72ca6709bf1d94121bf3748801b4"
    "0f6f5c8258404fefc7c718693b57c87170ceba220382afbdd148c0a53b4a009ca63ad1f1"
    "01483a6170c83a77f23d362a68dcb502802df7f98fa4f7a78b4082b211530e1234305850"
    "f770f6769ae9871d42b970fc6254bb927c2181fff45897f241bd72221d86d33c8df64c0a"
    "3c8cbb9aa52fef191d7202465c52df8d33727a38c7dc5d40864d753348a340f8afcbb3bb"
    "05d4a03f16b1080d825840fe682775f0fa232e909ddc9ec3210ea7a0ee6514cd8b081519"
    "0a08f7cef3985463152e10dfad9ed6c09b641b6c1824498e77814a7c12e03096a63cd620"
    "56446358500951ed3ef2065e4196d008b50a63bb3e2bdc9a64df67eff4e230b35429291d"
    "ef476684114e074357a5c834bf79eacf583b6fe9fcd1d17f3719a31de97aa4da5e4630b0"
    "5421359e0b6e4a9bd76c6b920b1909295820e7151e54578da8534352744c8ae184da9383"
    "6aae349d81755199ef8cd91272fc5820d298da3803eb9958f01c02e73f2410f2f9bb2ecb"
    "c346526b1b76772e1bdd7db500005840940f8a3696847e4a238705bdd27a345086282067"
    "b9bc5cb7b69847ca8756085844d576f59ab056c169a504320cc1eab4c11fd529482b3c57"
    "da6fa96d44635b0802005901c09a407cf27c08d92c740f86d8463ad755ae4c7cbcc6c2ed"
    "4a2b845daf4b250e2e36323a14af6a58a4ec614943f661daace19fbf6371693140ef1794"
    "06fd0c9d00cc6a5ab5c9b40f817c715df558af7d07b6186f0ccf31715ec2fb00980730ac"
    "166af657e6670608afe1bf651d496e01b1c7ff1eb44614d8cfd1b7e32b2c2939349236cc"
    "0ada145d8d8d7ad919ef1e60c8bbad31dbedf9f395849705a00c14a8785106aae31f55ab"
    "c5b1f2089cbef16d9401f158704c1e4f740f7125cfc700a99d97d0332eacb33e4bbc8dab"
    "2872ec2b3df9e113addaebd156bfc64fdfc732614d2aedd10a58a34993b7b08c822af3aa"
    "615b6bbb9b267bc902e4f1075e194aed084ca18f8bcde1a6b094bf3f5295a0d454c0a083"
    "ed5b74f7092fc0a7346c03979a30eeea76d686e512ba48d21544ba874886cdd166cbf275"
    "b11f1f3881f4c4277c09a24b88fc6168f4578267bdc9d62cb9b78b8dfc888ccce226a177"
    "725f39e7d50930552861d1e88b7898971c780dc3b773321ba1854422b5cecead7d50e777"
    "83050eeae2cd9595b9cd91681c72e5d53bb7d12f28dec9b2847ee70a3d7781fb1133aea3"
    "b169f536ff5945ec0a76950e51beded0627bb78120617a2f0842e50e39"
)


GOLDEN_BLOCK = ("/root/reference/ouroboros-consensus-shelley-test/test/"
                "golden/disk/Block")
CARDANO_BLOCK = ("/root/reference/ouroboros-consensus-cardano-test/test/"
                 "golden/CardanoNodeToNodeVersion3/Block_Shelley")


class TestRealBlock:
    """The CBOR-in-CBOR block parser on the reference's golden Shelley
    block (disk dialect + tag-24 network dialect), with the BBODY hash and
    the Ed25519 witness multi-verify checked on the real bytes."""

    def _load(self, path):
        if not os.path.exists(path):
            pytest.skip("reference not mounted")
        return open(path, "rb").read()

    def test_parse_and_reencode(self):
        raw = self._load(GOLDEN_BLOCK)
        blk = SC.parse_block(raw)
        assert blk.header.body.slot == 9
        assert len(blk.txs) == 1
        assert len(blk.txs[0].witnesses) == 2
        assert blk.to_cbor() == raw

    def test_body_hash_matches_header_claim(self):
        blk = SC.parse_block(self._load(GOLDEN_BLOCK))
        assert blk.computed_body_hash() == blk.header.body.body_hash

    def test_witnesses_sign_the_tx_body_hash(self):
        from ouroboros_tpu.crypto import ed25519_ref
        blk = SC.parse_block(self._load(GOLDEN_BLOCK))
        tx = blk.txs[0]
        for vk, sig in tx.witnesses:
            assert ed25519_ref.verify(vk, tx.body_hash, sig)
            assert not ed25519_ref.verify(vk, b"x" + tx.body_hash[1:], sig)

    def test_full_block_validation(self):
        blk = SC.parse_block(self._load(GOLDEN_BLOCK))
        # bsize in the golden example is a hand-written constant
        # (Examples.hs:448), so the size check is off; everything else —
        # header crypto, BBODY hash, witnesses — is real
        assert SC.validate_block(blk, ALPHA_NONCE, ALPHA_LEADER,
                                 CpuRefBackend(), check_body_size=False)

    def test_tampered_witness_rejected(self):
        from dataclasses import replace
        blk = SC.parse_block(self._load(GOLDEN_BLOCK))
        # flip a byte inside the witness section: the BBODY hash check
        # catches it before any signature is even looked at
        bad_wits = blk.wits_bytes[:-1] + bytes([blk.wits_bytes[-1] ^ 1])
        bad = replace(blk, wits_bytes=bad_wits)
        assert not SC.validate_block(bad, ALPHA_NONCE, ALPHA_LEADER,
                                     CpuRefBackend(),
                                     check_body_size=False)

    def test_network_dialect_parses_identically(self):
        disk = SC.parse_block(self._load(GOLDEN_BLOCK))
        net = SC.parse_block(self._load(CARDANO_BLOCK))
        assert net.header.body == disk.header.body
        assert net.bodies_bytes == disk.bodies_bytes


GOLDEN_TX = ("/root/reference/ouroboros-consensus-cardano-test/test/golden/"
             "CardanoNodeToNodeVersion3/GenTx_Shelley")
GOLDEN_TXID = ("/root/reference/ouroboros-consensus-cardano-test/test/"
               "golden/CardanoNodeToNodeVersion3/GenTxId_Shelley")
GOLDEN_TX_ALLEGRA = ("/root/reference/ouroboros-consensus-cardano-test/"
                     "test/golden/CardanoNodeToNodeVersion3/GenTx_Allegra")


class TestRealTx:
    """Standalone real Shelley transactions (the GenTx wire form)."""

    def _load(self, path):
        if not os.path.exists(path):
            pytest.skip("reference not mounted")
        return open(path, "rb").read()

    def test_txid_matches_reference_golden(self):
        from ouroboros_tpu.utils import cbor
        tx = SC.parse_tx(self._load(GOLDEN_TX))
        golden = cbor.loads(self._load(GOLDEN_TXID))
        assert tx.body_hash == bytes(golden[1])

    def test_witnesses_verify_and_tamper_rejects(self):
        tx = SC.parse_tx(self._load(GOLDEN_TX))
        assert len(tx.witnesses) == 2
        assert SC.validate_tx(tx, CpuRefBackend())
        from dataclasses import replace
        vk, sig = tx.witnesses[0]
        bad = replace(tx, witnesses=((vk, sig[:-1] + bytes([sig[-1] ^ 1])),)
                      + tx.witnesses[1:])
        assert not SC.validate_tx(bad, CpuRefBackend())

    def test_block_tx_equals_standalone_tx(self):
        """The tx inside the golden block and the standalone GenTx golden
        are the same example transaction, byte-identical body."""
        blk = SC.parse_block(self._load(
            "/root/reference/ouroboros-consensus-shelley-test/test/golden/"
            "disk/Block"))
        tx = SC.parse_tx(self._load(GOLDEN_TX))
        assert blk.txs[0].body_bytes == tx.body_bytes
        assert blk.txs[0].witnesses == tx.witnesses

    def test_allegra_tx_parses(self):
        # Allegra txs share the Shelley shape at this version; the third
        # element (auxiliary data) differs structurally but stays opaque
        tx = SC.parse_tx(self._load(GOLDEN_TX_ALLEGRA))
        assert tx.witnesses and SC.validate_tx(tx, CpuRefBackend())


def test_chain_dep_state_golden_parses():
    """The reference's serialised TPraos protocol state (the snapshot
    format resume reads) parses into the fields our TPraosState tracks."""
    path = ("/root/reference/ouroboros-consensus-shelley-test/test/golden/"
            "disk/ChainDepState")
    if not os.path.exists(path):
        pytest.skip("reference not mounted")
    st = SC.parse_chain_dep_state(open(path, "rb").read())
    assert st.last_slot == 1
    assert len(st.counters) == 2
    for kh, n in st.counters:
        assert len(kh) == 28 and n in (1, 2)
    assert st.eta_v and st.eta_c and st.eta_ph and st.lab
    assert st.eta0 is None            # NeutralNonce in the example
