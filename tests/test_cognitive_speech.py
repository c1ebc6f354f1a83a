"""Streaming speech SDK + Azure Search index management (review round 1
item 9) against local mock services: pull-audio reads, VAD utterance
segmentation, partial-result assembly, conversation transcription
speaker attribution, and the index management API."""

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from mmlspark_tpu.core import DataFrame
from mmlspark_tpu.cognitive import (AzureSearchWriter,
                                    ConversationTranscription,
                                    PullAudioInputStream, SpeechToTextSDK,
                                    segment_pcm16, validate_index_fields)

RATE = 16000


def tone(seconds: float, freq=440.0, amp=8000):
    t = np.arange(int(seconds * RATE)) / RATE
    return (amp * np.sin(2 * np.pi * freq * t)).astype(np.int16)


def silence(seconds: float):
    return np.zeros(int(seconds * RATE), np.int16)


def three_utterances():
    """~0.5s tone, 0.5s gap, 0.7s tone, 0.5s gap, 0.4s tone."""
    return np.concatenate([
        silence(0.2), tone(0.5), silence(0.5), tone(0.7, 550),
        silence(0.5), tone(0.4, 660), silence(0.2)])


@pytest.fixture(scope="module")
def speech_api():
    """Mock STT endpoint: DisplayText reports the byte count so tests can
    tie responses to the audio that was posted; /transcribe adds a
    SpeakerId."""
    class Handler(BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(n) if n else b""
            ct = self.headers.get("Content-Type", "")
            txt = f"heard {len(body)} bytes"
            if not ct.startswith("audio/wav"):
                txt += f" as {ct}"   # compressed path: codec label
            out = {"RecognitionStatus": "Success",
                   "DisplayText": txt,
                   "Offset": 0, "Duration": 0}
            if self.path.startswith("/transcribe"):
                out["SpeakerId"] = "Guest_0"
            payload = json.dumps(out).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)

        def log_message(self, *a):
            pass

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{httpd.server_address[1]}"
    httpd.shutdown()


class TestPullStream:
    def test_fixed_frames_from_bytes(self):
        data = bytes(range(256)) * 10
        s = PullAudioInputStream(data, frame_bytes=300)
        frames = []
        while True:
            f = s.read()
            if not f:
                break
            frames.append(f)
        assert b"".join(frames) == data
        assert all(len(f) == 300 for f in frames[:-1])

    def test_callable_source(self):
        chunks = [b"abc", b"defgh", b""]
        it = iter(chunks)
        s = PullAudioInputStream(lambda: next(it), frame_bytes=4)
        out = b""
        while True:
            f = s.read()
            if not f:
                break
            out += f
        assert out == b"abcdefgh"


class TestVAD:
    def test_three_utterances_found(self):
        segs = segment_pcm16(three_utterances(), RATE)
        assert len(segs) == 3
        # ordered, non-overlapping, each covering roughly the tone lengths
        durations = [(e - s) / RATE for s, e in segs]
        assert 0.3 < durations[0] < 0.8
        assert 0.5 < durations[1] < 1.0
        assert 0.25 < durations[2] < 0.7
        assert all(segs[i][1] <= segs[i + 1][0] for i in range(2))

    def test_max_segment_cap(self):
        segs = segment_pcm16(tone(5.0), RATE, max_segment_s=1.0)
        assert len(segs) >= 4
        assert all((e - s) / RATE <= 1.05 for s, e in segs)

    def test_silence_only(self):
        assert segment_pcm16(silence(1.0), RATE) == []


class TestStreamingSDK:
    def test_final_results_per_utterance(self, speech_api):
        sdk = SpeechToTextSDK(url=f"{speech_api}/stt", outputCol="text")
        sdk.set("subscriptionKey", "k")
        sdk.setAudioDataCol("audio")
        audio = np.empty(1, object)
        audio[0] = three_utterances().tobytes()
        out = sdk.transform(DataFrame({"audio": audio}))
        rows = list(out["text"])
        assert len(rows) == 3
        assert all(r["RecognitionStatus"] == "Success" for r in rows)
        assert all(r["DisplayText"].startswith("heard") for r in rows)
        offsets = [r["Offset"] for r in rows]
        assert offsets == sorted(offsets) and offsets[0] > 0
        assert all(r["Duration"] > 0 for r in rows)
        assert list(out["sourceRow"]) == [0, 0, 0]

    def test_intermediate_hypotheses(self, speech_api):
        sdk = SpeechToTextSDK(url=f"{speech_api}/stt", outputCol="text")
        sdk.set("subscriptionKey", "k")
        sdk.set("streamIntermediateResults", True)
        sdk.set("intermediateInterval", 0.2)
        sdk.setAudioDataCol("audio")
        audio = np.empty(1, object)
        audio[0] = np.concatenate([tone(0.8), silence(0.5)]).tobytes()
        out = sdk.transform(DataFrame({"audio": audio}))
        statuses = [r["RecognitionStatus"] for r in out["text"]]
        assert statuses[-1] == "Success"
        assert statuses.count("Recognizing") >= 2
        # hypotheses grow monotonically within the utterance
        partial_bytes = [int(r["DisplayText"].split()[1])
                         for r in out["text"]]
        assert partial_bytes == sorted(partial_bytes)

    def test_multiple_rows_tagged(self, speech_api):
        sdk = SpeechToTextSDK(url=f"{speech_api}/stt", outputCol="text")
        sdk.set("subscriptionKey", "k")
        sdk.setAudioDataCol("audio")
        audio = np.empty(2, object)
        audio[0] = np.concatenate([tone(0.4), silence(0.4)]).tobytes()
        audio[1] = three_utterances().tobytes()
        out = sdk.transform(DataFrame({"audio": audio}))
        src = list(out["sourceRow"])
        assert src.count(0) == 1 and src.count(1) == 3


class TestConversationTranscription:
    def test_speaker_attribution_and_participants(self, speech_api):
        ct = ConversationTranscription(url=f"{speech_api}/transcribe",
                                       outputCol="text")
        ct.set("subscriptionKey", "k")
        ct.setAudioDataCol("audio")
        ct.set("participantsJson", json.dumps(
            [{"name": "alice", "language": "en-US"},
             {"name": "bob", "language": "en-US"}]))
        audio = np.empty(1, object)
        audio[0] = np.concatenate([tone(0.4), silence(0.4)]).tobytes()
        out = ct.transform(DataFrame({"audio": audio}))
        rows = list(out["text"])
        assert len(rows) == 1
        assert rows[0]["SpeakerId"] == "Guest_0"

    def test_url_template(self):
        ct = ConversationTranscription(outputCol="t")
        ct.setLocation("eastus")
        assert "transcribe.eastus.cts.speech" in ct.get("url")


class TestAzureSearchIndexManagement:
    def test_validate_index_fields(self):
        ok = validate_index_fields({
            "id": {"type": "Edm.String", "key": True},
            "score": "Edm.Double"})
        assert [f["name"] for f in ok] == ["id", "score"]
        with pytest.raises(ValueError, match="exactly one"):
            validate_index_fields({"a": "Edm.String"})
        with pytest.raises(ValueError, match="exactly one"):
            validate_index_fields({
                "a": {"type": "Edm.String", "key": True},
                "b": {"type": "Edm.String", "key": True}})
        with pytest.raises(ValueError, match="invalid EDM"):
            validate_index_fields({"a": {"type": "Edm.Bogus", "key": True}})

    def test_invalid_action_rejected(self):
        with pytest.raises(ValueError, match="action"):
            AzureSearchWriter(service_name="s", index_name="i", key="k",
                              action="replace")

    def test_management_calls(self):
        """Index management against a stateful mock registry."""
        indexes: dict[str, dict] = {}

        class Handler(BaseHTTPRequestHandler):
            def _respond(self, code, obj=None):
                payload = json.dumps(obj or {}).encode()
                self.send_response(code)
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def do_GET(self):
                path = self.path.split("?")[0]
                if path == "/indexes":
                    self._respond(200, {"value": [
                        {"name": n} for n in indexes]})
                elif path.endswith("/stats"):
                    name = path.split("/")[2]
                    if name in indexes:
                        self._respond(200, {"documentCount": 0,
                                            "storageSize": 0})
                    else:
                        self._respond(404)
                else:
                    name = path.split("/")[2]
                    self._respond(200 if name in indexes else 404)

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n))
                if self.path.split("?")[0] == "/indexes":
                    indexes[body["name"]] = body
                    self._respond(201, body)
                else:
                    self._respond(200, {"value": []})

            def do_DELETE(self):
                name = self.path.split("?")[0].split("/")[2]
                self._respond(204 if indexes.pop(name, None) else 404)

            def log_message(self, *a):
                pass

        httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            base = f"http://127.0.0.1:{httpd.server_address[1]}/indexes"
            w = AzureSearchWriter(
                service_name="x", index_name="idx1", key="k",
                index_fields={"id": {"type": "Edm.String", "key": True},
                              "text": "Edm.String"},
                base_url=base)
            assert not w.index_exists()
            assert w.ensure_index()      # created
            assert w.index_exists()
            assert not w.ensure_index()  # second call: already exists
            assert w.list_indexes() == ["idx1"]
            assert w.get_statistics()["documentCount"] == 0
            assert w.delete_index()
            assert not w.index_exists()
        finally:
            httpd.shutdown()


class TestWavContainer:
    def _wav_bytes(self, samples: np.ndarray, rate: int, channels=1):
        import io
        import wave
        buf = io.BytesIO()
        with wave.open(buf, "wb") as w:
            w.setnchannels(channels)
            w.setsampwidth(2)
            w.setframerate(rate)
            w.writeframes(samples.tobytes())
        return buf.getvalue()

    def test_parse_wav_roundtrip(self):
        from mmlspark_tpu.cognitive.speech import parse_wav
        pcm = tone(0.2)
        data = self._wav_bytes(pcm, 16000)
        samples, rate = parse_wav(data)
        assert rate == 16000
        np.testing.assert_array_equal(samples, pcm)

    def test_parse_wav_stereo_downmix(self):
        from mmlspark_tpu.cognitive.speech import parse_wav
        left = tone(0.1)
        right = np.zeros_like(left)
        inter = np.empty(left.size * 2, np.int16)
        inter[0::2], inter[1::2] = left, right
        samples, rate = parse_wav(self._wav_bytes(inter, 8000, channels=2))
        expected = (left.astype(np.float64) / 2).astype(np.int16)
        np.testing.assert_array_equal(samples, expected)
        assert rate == 8000

    def test_parse_wav_rejects_garbage(self):
        import pytest
        from mmlspark_tpu.cognitive.speech import parse_wav
        with pytest.raises(ValueError, match="RIFF"):
            parse_wav(b"not a wav file")

    def test_sdk_auto_detects_wav_and_uses_its_rate(self, speech_api):
        # 8 kHz WAV: offsets/durations must be computed at 8 kHz
        sdk = SpeechToTextSDK(url=f"{speech_api}/stt", outputCol="text")
        sdk.set("subscriptionKey", "k")
        sdk.setAudioDataCol("audio")
        rate = 8000
        t = np.arange(int(0.4 * rate)) / rate
        pcm = np.concatenate([
            (8000 * np.sin(2 * np.pi * 440 * t)).astype(np.int16),
            np.zeros(rate // 2, np.int16)])
        audio = np.empty(1, object)
        audio[0] = self._wav_bytes(pcm, rate)
        out = sdk.transform(DataFrame({"audio": audio}))
        rows = list(out["text"])
        assert len(rows) == 1
        dur_s = rows[0]["Duration"] / 1e7
        assert 0.3 < dur_s < 0.55, dur_s  # ~0.4s at the WAV's own rate

    def test_bad_wav_is_per_row_error(self, speech_api):
        sdk = SpeechToTextSDK(url=f"{speech_api}/stt", outputCol="text",
                              fileType="wav")
        sdk.set("subscriptionKey", "k")
        sdk.setAudioDataCol("audio")
        audio = np.empty(2, object)
        audio[0] = b"RIFF but truncated garbage"
        audio[1] = self._wav_bytes(
            np.concatenate([tone(0.3), silence(0.4)]), 16000)
        out = sdk.transform(DataFrame({"audio": audio}))
        by_src = {int(s): (r, e) for s, r, e in
                  zip(out["sourceRow"], out["text"], out["error"])}
        assert by_src[0][0]["RecognitionStatus"] == "Error"
        assert by_src[0][1] is not None
        assert by_src[1][0]["RecognitionStatus"] == "Success"

    def test_file_type_validated(self):
        import pytest
        # mp3/ogg are valid since the CompressedStream equivalent landed
        sdk = SpeechToTextSDK(outputCol="t", fileType="flac")
        sdk.set("subscriptionKey", "k")
        sdk.setAudioDataCol("audio")
        audio = np.empty(1, object)
        audio[0] = b"\x00\x00"
        with pytest.raises(ValueError, match="fileType"):
            sdk.transform(DataFrame({"audio": audio}))


def mp3_frame(bitrate_idx=9, rate_idx=0, fill=0x55):
    """One valid MPEG1 Layer III frame (128 kbps @ 44.1 kHz by default:
    144*128000/44100 = 417 bytes, 1152 samples = 26.12 ms)."""
    hdr = bytes([0xFF, 0xFB, (bitrate_idx << 4) | (rate_idx << 2), 0])
    size = 144 * 128000 // 44100
    return hdr + bytes([fill]) * (size - 4)


def ogg_page(granule, seq, body=b"\x01" * 100):
    return (b"OggS" + b"\x00\x00"
            + int(granule).to_bytes(8, "little")
            + (1234).to_bytes(4, "little")
            + int(seq).to_bytes(4, "little")
            + b"\x00\x00\x00\x00"
            + bytes([1, len(body)]) + body)


class TestCompressedAudio:
    """MP3/OGG streaming without local decode (reference
    CompressedStream, SpeechToTextSDK.scala:341-346): container frames
    parsed for boundaries + timing, chunks labeled with their codec."""

    def test_mp3_frame_walk_and_id3_skip(self):
        from mmlspark_tpu.cognitive.audio_codecs import parse_mp3_units
        frames = b"".join(mp3_frame() for _ in range(10))
        units = parse_mp3_units(frames)
        assert len(units) == 10
        assert all(u.size == 417 for u in units)
        assert abs(units[0].duration_s - 1152 / 44100) < 1e-9
        # ID3v2 tag (sync-safe size 200) is skipped, chain still found
        id3 = b"ID3\x04\x00\x00" + bytes([0, 0, 200 >> 7, 200 & 0x7F]) \
            + b"\x00" * 200
        assert len(parse_mp3_units(id3 + frames)) == 10
        # truncated final frame is dropped, not mis-parsed
        assert len(parse_mp3_units(frames[:-50])) == 9
        with pytest.raises(ValueError, match="no MPEG"):
            parse_mp3_units(b"\x00" * 1000)

    def test_ogg_page_walk_and_granule_timing(self):
        from mmlspark_tpu.cognitive.audio_codecs import parse_ogg_units
        pages = b"".join(ogg_page(4800 * (i + 1), i) for i in range(5))
        units = parse_ogg_units(pages)
        assert len(units) == 5
        # granule clock is 48 kHz: 4800-granule steps = 0.1 s pages
        assert all(abs(u.duration_s - 0.1) < 1e-9 for u in units[1:])
        with pytest.raises(ValueError, match="not an OGG"):
            parse_ogg_units(b"junk" * 100)

    def test_chunks_respect_frame_boundaries(self):
        from mmlspark_tpu.cognitive.audio_codecs import (chunk_units,
                                                         parse_mp3_units)
        data = b"".join(mp3_frame() for _ in range(10))
        units = parse_mp3_units(data)
        chunks = chunk_units(units, 0.06, data)  # 2 frames ≈ 0.052 s
        assert len(chunks) == 5
        for k, (blob, off_s, dur_s, u0, u1) in enumerate(chunks):
            assert (u0, u1) == (2 * k, 2 * k + 2)
            assert len(blob) == 2 * 417          # whole frames only
            assert blob[:2] == b"\xff\xfb"       # starts on a sync word
            assert abs(off_s - k * 2 * 1152 / 44100) < 1e-6
            assert abs(dur_s - 2 * 1152 / 44100) < 1e-6
        # chunk bytes reassemble the original stream exactly
        assert b"".join(c[0] for c in chunks) == data

    def test_sdk_streams_mp3_with_codec_content_type(self, speech_api):
        sdk = SpeechToTextSDK(url=f"{speech_api}/stt", outputCol="text",
                              maxSegmentSeconds=0.06)
        sdk.set("subscriptionKey", "k")
        sdk.setAudioDataCol("audioData")
        audio = np.empty(1, object)
        audio[:] = [b"".join(mp3_frame() for _ in range(4))]
        out = sdk.transform(DataFrame({"audioData": audio}))
        rows = out["text"]
        assert len(rows) == 2                    # 2 frames per chunk
        for k, r in enumerate(rows):
            assert r["RecognitionStatus"] == "Success"
            assert r["DisplayText"].endswith("as audio/mpeg")
            assert "834 bytes" in r["DisplayText"]   # 2 whole frames
            want_off = int(k * 2 * 1152 / 44100 * 10_000_000)
            assert abs(r["Offset"] - want_off) <= 1
        # ogg rides the same path with its own label
        audio[:] = [b"".join(ogg_page(4800 * (i + 1), i)
                             for i in range(3))]
        rows = sdk.transform(DataFrame({"audioData": audio}))["text"]
        assert all(r["DisplayText"].endswith("as audio/ogg")
                   for r in rows)

    def test_bad_compressed_row_prefails_not_batch(self, speech_api):
        sdk = SpeechToTextSDK(url=f"{speech_api}/stt", outputCol="text",
                              fileType="mp3")
        sdk.set("subscriptionKey", "k")
        sdk.setAudioDataCol("audioData")
        audio = np.empty(2, object)
        audio[:] = [b"\x00" * 64, b"".join(mp3_frame()
                                           for _ in range(2))]
        out = sdk.transform(DataFrame({"audioData": audio}))
        by_src = {int(s): r for s, r in zip(out["sourceRow"],
                                            out["text"])}
        assert by_src[0]["RecognitionStatus"] == "Error"
        assert by_src[1]["RecognitionStatus"] == "Success"

    def test_raw_pcm_sync_collision_falls_back(self, speech_api):
        """Raw PCM whose first int16 sample is -1 starts with FF FF —
        a valid MP3 sync pattern. Auto mode must still transcribe it as
        the raw audio it is (chained-frame requirement), not error or
        mislabel it audio/mpeg."""
        sdk = SpeechToTextSDK(url=f"{speech_api}/stt", outputCol="text")
        sdk.set("subscriptionKey", "k")
        sdk.setAudioDataCol("audio")
        pcm = np.concatenate([tone(0.4), silence(0.4)])
        pcm[0] = -1                      # bytes FF FF: MP3 sync collide
        audio = np.empty(1, object)
        audio[0] = pcm.tobytes()
        rows = list(sdk.transform(DataFrame({"audio": audio}))["text"])
        assert len(rows) == 1
        assert rows[0]["RecognitionStatus"] == "Success"
        assert "as audio/" not in rows[0]["DisplayText"]  # raw PCM path

    def test_vorbis_granule_clock_sniffed(self):
        """A Vorbis id header in the first page switches the granule
        clock to the stream's own sample rate (no decoding — header
        fields only); Opus/unknown streams keep the 48 kHz default."""
        from mmlspark_tpu.cognitive.audio_codecs import parse_ogg_units
        ident = (b"\x01vorbis" + b"\x00\x00\x00\x00" + b"\x02"
                 + (44100).to_bytes(4, "little") + b"\x00" * 16)
        pages = ogg_page(0, 0, body=ident) + b"".join(
            ogg_page(44100 * (i + 1), i + 1) for i in range(3))
        units = parse_ogg_units(pages)
        assert all(abs(u.duration_s - 1.0) < 1e-9 for u in units[1:])

    def test_compressed_partials_on_frame_boundaries(self, speech_api):
        """streamIntermediateResults works for compressed rows too:
        growing chunk prefixes sliced on frame boundaries."""
        sdk = SpeechToTextSDK(url=f"{speech_api}/stt", outputCol="text",
                              maxSegmentSeconds=0.3)
        sdk.set("subscriptionKey", "k")
        sdk.set("streamIntermediateResults", True)
        sdk.set("intermediateInterval", 0.05)  # ~every 2 frames
        sdk.setAudioDataCol("audio")
        audio = np.empty(1, object)
        audio[0] = b"".join(mp3_frame() for _ in range(8))
        rows = list(sdk.transform(DataFrame({"audio": audio}))["text"])
        statuses = [r["RecognitionStatus"] for r in rows]
        assert statuses[-1] == "Success"
        assert statuses.count("Recognizing") >= 2
        # every partial is whole frames, growing monotonically
        sizes = [int(r["DisplayText"].split()[1]) for r in rows]
        assert all(s % 417 == 0 for s in sizes)
        assert sizes == sorted(sizes)
